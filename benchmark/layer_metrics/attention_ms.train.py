"""Device time of the attention blocks, in ms a step: the leaf time the
reduced trace puts under the kind ``attention`` (the configuration's
``BLOCKS``: projections, kernels, rotary, gates, the norm that goes with
the block; forward and backward, a recomputing backward's forward with it)
over the steps in the traced window.  None untraced, or where the kind has
no time."""
from benchmark import device_time


def read(facts):
    return device_time.block_ms(facts, "attention")
