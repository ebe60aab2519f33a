"""Routing imbalance of the sparse experts: the tokens of the most-loaded
HELD expert over the mean of the held experts, in the worst layer, at the
last step of the window.  1 is an even router; the grouped products' time
follows the total, but the longest group bounds how well their tiles fill.

From the counts every ``SparseExperts`` block keeps as non-trainable state
(``mxnet_tpu.gluon.contrib.nn.publish_routing_counts``, which also sets
the ``moe.*`` gauges in ``telemetry``); None where the program has no such
block."""


def read(facts):
    try:
        from mxnet_tpu.gluon.contrib.nn import publish_routing_counts
    except ImportError:
        return None
    worst = None
    for record in publish_routing_counts().values():
        first, end = record["held"]
        held = record["load"][first:end]
        if sum(held) > 0:
            ratio = max(held) * len(held) / sum(held)
            worst = ratio if worst is None else max(worst, ratio)
    return worst
