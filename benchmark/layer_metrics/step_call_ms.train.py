"""Host time of the jitted call inside one step — flattening some hundreds
of leaves, the C++ dispatch, any wait on the runtime: the program's span
``parallel.step.call``, median over the window's steps."""
import statistics

from benchmark import program_spans


def read(facts):
    steps = program_spans.window_steps(facts)
    return statistics.median(s["call_ms"] for s in steps) if steps else None
