"""Device time of the sparse experts, in ms a step: the leaf time the
reduced trace puts under the kind ``experts`` (the configuration's
``BLOCKS``: the router, the routed experts, their dispatch and combine, the
norm that feeds the router; forward and backward, a recomputing backward's
forward with it) over the steps in the traced window.  A shared expert is
``ffn``'s.  None untraced, or where the kind has no time."""
from benchmark import device_time


def read(facts):
    return device_time.block_ms(facts, "experts")
