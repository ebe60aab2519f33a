"""Host time of the step builder's own Python in one step — cache key and
lookup, learning-rate refresh, parameter gather, master resync, writeback
into a few hundred parameters: the program's span ``parallel.step`` minus
its children ``parallel.step.place`` and ``parallel.step.call``, median over
the window's steps."""
import statistics

from benchmark import program_spans


def read(facts):
    steps = program_spans.window_steps(facts)
    return statistics.median(s["self_ms"] for s in steps) if steps else None
