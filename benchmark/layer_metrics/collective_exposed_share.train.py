"""Device time of the collectives that is NOT hidden behind compute, over
the device's busy time in the traced window, in %: the ``all-gather``,
``reduce-scatter`` and ``all-reduce`` operations on the ``XLA Ops`` line
(where the TensorCore waits for them: the asynchronous part rides on
``Async XLA Ops`` and is not there), their ``-start`` / ``-done`` halves
included.

From ``trace["device_ops"]``, the ten operations that took most time:
None where no collective is among them."""

COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce")


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    seconds = [s for name, s in trace["device_ops"]
               if name.startswith(COLLECTIVES)]
    if not seconds:
        return None
    return 100.0 * sum(seconds) / trace["busy_s"]
