"""The program's own step spans, read back from its memory: the last
``facts["steps"]`` ``parallel.step`` spans of ``mxnet_tpu.telemetry`` with
the durations of their children ``parallel.step.place`` (the batch to the
device) and ``parallel.step.call`` (the jitted call).

The train drivers open no window in the program's telemetry, and need not:
``run`` of every ``configs/*/model.py`` is one ``step(data, label)``, one
span a step, and nothing calls the step after the window, so the window's
steps are the last ones the program recorded.  Where the program holds
fewer than that, has no such spans or no way to read them (a program from
before they existed), there is nothing to read: None, never a number from
part of the window.
"""


def window_steps(facts):
    """``[{"call_ms", "self_ms"}]``, one per step of the window, oldest
    first — or None."""
    steps = facts.get("steps")
    try:
        from mxnet_tpu.telemetry import recent_spans
    except ImportError:
        return None
    if not steps:
        return None
    spans, short = recent_spans("parallel.step", steps)
    if short:
        return None
    out = []
    for span in spans:
        kids = span["children"]
        if not {"parallel.step.place", "parallel.step.call"} <= set(kids):
            return None
        out.append({"call_ms": kids["parallel.step.call"],
                    "self_ms": span["dur_ms"] - sum(kids.values())})
    return out
