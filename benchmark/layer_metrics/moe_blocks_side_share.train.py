"""Share of the expert layers that ran their dense products over blocks of
slots at the last step of the window, of the layers that have blocks, in %:
100 where every layer's held routes fit its blocks; a layer under it took
the ragged side of ``ops.moe.sparse_ffn``'s ``lax.cond``, whose time follows
the routing (exact, slower).

From the gauges ``moe.layers_on_blocks`` / ``moe.layers_with_blocks`` that
``publish_routing_counts`` sets from the blocks' state by the rule the step
tests on the device; None where the program sets no such gauge, has no such
block, or none of its layers has blocks (one flat route a token)."""


def read(facts):
    try:
        from mxnet_tpu import telemetry
        from mxnet_tpu.gluon.contrib.nn import publish_routing_counts
    except ImportError:
        return None
    if not publish_routing_counts():
        return None
    gauges = telemetry.snapshot()["gauges"]
    if not gauges.get("moe.layers_with_blocks"):
        return None
    return 100.0 * gauges["moe.layers_on_blocks"] \
        / gauges["moe.layers_with_blocks"]
