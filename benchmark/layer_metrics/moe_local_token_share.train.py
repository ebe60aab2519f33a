"""Share of the routes that go to experts held on this chip, over all the
layers, at the last step of the window, in %: what the chip's grouped
products cover of the tokens (about held / all experts under an even
router; the rest is the absent chips' part of the layer).

From the gauges ``moe.tokens_local`` / ``moe.tokens_routed`` that
``publish_routing_counts`` sets from the blocks' state; None where the
program has no such block."""


def read(facts):
    try:
        from mxnet_tpu import telemetry
        from mxnet_tpu.gluon.contrib.nn import publish_routing_counts
    except ImportError:
        return None
    if not publish_routing_counts():
        return None
    gauges = telemetry.snapshot()["gauges"]
    return 100.0 * gauges["moe.tokens_local"] / gauges["moe.tokens_routed"]
