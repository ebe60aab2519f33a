"""Host time of one ``step(...)`` call returning (the enqueue of an
asynchronous dispatch), median over the window, from the benchmark's own
span ``bench.step_dispatch`` round the call."""
import statistics


def read(facts):
    spans = facts["spans"].get("bench.step_dispatch")
    return statistics.median(spans) * 1e3 if spans else None
