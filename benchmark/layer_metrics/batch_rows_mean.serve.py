"""Requests answered per dispatch: the program's counters ``serve.results``
over ``serve.dispatches``, the window's share of each."""


def read(facts):
    counters = facts.get("counters", {})
    dispatches = counters.get("serve.dispatches")
    return counters["serve.results"] / dispatches if dispatches else None
