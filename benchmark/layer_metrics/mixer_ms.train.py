"""Device time of the Mamba-2 mixers, in ms a step: the leaf time the
reduced trace puts under the kind ``mixer`` (the configuration's
``BLOCKS``: the projections, the convolution, the scan's kernels and the
rows made for them, the gated norm, the layer's pre-norm; forward and
backward) over the steps in the traced window.  None untraced, or where the
kind has no time."""
from benchmark import device_time


def read(facts):
    return device_time.block_ms(facts, "mixer")
