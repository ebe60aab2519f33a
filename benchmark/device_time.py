"""What the readers of device time share: the steps in the traced window, a
block kind's time a step, the named kernels' seconds and calls, the routes
to held experts, and a share of a peak — from the reduced trace
(``reduce_trace.reduce``: ``blocks``, ``kernels``) and the blocks' state.
Each returns None where there is nothing to read."""


def steps_in_window(facts):
    """The traced window over the median step, as
    ``flash_masked_roofline.train`` counts the steps in it."""
    trace, step_s = facts.get("trace"), facts.get("step_s_median")
    if not trace or not step_s:
        return None
    return trace["window_s"] / step_s


def block_ms(facts, kind):
    """The leaf device time of a block kind, all phases together, in ms a
    step; None where the trace has no blocks or the kind no time."""
    steps = steps_in_window(facts)
    blocks = facts["trace"].get("blocks") if steps else None
    if not blocks or not blocks.get(kind):
        return None
    return 1e3 * sum(blocks[kind].values()) / steps


def kernel_time(facts, names):
    """``(seconds, calls)`` of the kernels ``names`` in the traced window,
    summed, means over the chips; None where one of them did not run."""
    trace = facts.get("trace") or {}
    found = {n: (s, c) for n, s, c in trace.get("kernels") or ()}
    if any(n not in found for n in names):
        return None
    return (sum(found[n][0] for n in names),
            sum(found[n][1] for n in names))


def held_routes():
    """The routes to held experts at the last step, one count a layer that
    has experts — the blocks' state, as ``moe_local_token_share.train``
    reads it; None where the program has no such layer."""
    try:
        from mxnet_tpu.gluon.contrib.nn import publish_routing_counts
    except ImportError:
        return None
    counts = publish_routing_counts()
    if not counts:
        return None
    return [sum(v["load"][v["held"][0]:v["held"][1]])
            for v in counts.values()]


def share(work, seconds, peak):
    """``work`` (operations or bytes) done in ``seconds`` of device time as
    a share of ``peak`` (a rate), in %; None where either is missing."""
    if not work or not seconds or not peak:
        return None
    return 100.0 * work / (seconds * peak)
