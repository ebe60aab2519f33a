"""Programs compiled or reloaded inside the measured window: jax's
monitoring events plus the server's own ``steady_state_recompiles()``.
Should be 0."""


def read(facts):
    if "compiles_in_window" not in facts:
        return None
    return facts["compiles_in_window"] + facts.get(
        "steady_state_recompiles", 0)
