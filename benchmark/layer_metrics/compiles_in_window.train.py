"""Programs jax handed its backend inside the measured window (compiled or
reloaded from the cache), from jax's monitoring events.  Should be 0."""


def read(facts):
    return facts.get("compiles_in_window")
