"""Device time of the Pallas kernels (``tpu_custom_call`` events of the
trace's ``XLA Ops``) over the device's busy time in the traced window."""


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["pallas_s"] / trace["busy_s"]
