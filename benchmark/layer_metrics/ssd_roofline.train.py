"""The state-space scan's kernels' share of the chip's bf16 peak, in %: the
chunked scan's products in a step (the configuration's ``ssd_flops`` at the
published chunk, forward and backward, no recomputation) over the device
seconds a step of ``ssd_fwd`` + ``ssd_states`` + ``ssd_bwd``, over the
published peak.  None untraced, where one of the three did not run, or
where the configuration counts no scan."""
from benchmark import device_time

KERNELS = ("ssd_fwd", "ssd_states", "ssd_bwd")


def read(facts):
    count = getattr(facts.get("model"), "ssd_flops", None)
    steps = device_time.steps_in_window(facts)
    timed = device_time.kernel_time(facts, KERNELS)
    if count is None or not steps or timed is None \
            or not facts.get("peaks"):
        return None
    return device_time.share(
        count(facts["sizes"]) * facts["global_batch"] * steps,
        timed[0] * facts["chips"], facts["peaks"]["bf16_flops_per_s"])
