"""Device time of the linear-attention layers, in ms a step: the leaf time
the reduced trace puts under the kinds ``linear_attention`` (the
configuration's ``BLOCKS``: the layers' pre-norm, input and output
projections, the convolution, norms and gates round the rule) and
``delta_scan`` (the chunked delta rule's own scopes ``delta.*``), forward
and backward, over the steps in the traced window.  None untraced, or
where neither kind has time."""
from benchmark import device_time


def read(facts):
    parts = [device_time.block_ms(facts, kind)
             for kind in ("linear_attention", "delta_scan")]
    if all(part is None for part in parts):
        return None
    return sum(part or 0.0 for part in parts)
