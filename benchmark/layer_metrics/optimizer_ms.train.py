"""Device time of the optimizer, in ms a step: the leaf time of the
operations under the step's ``optimizer`` scope (the kind ``optimizer``)
over the steps in the traced window.  What XLA fuses of the update into a
weight gradient's fusion carries the backward's scope and stands under that
block.  None untraced, or where the scope has no time."""
from benchmark import device_time


def read(facts):
    return device_time.block_ms(facts, "optimizer")
