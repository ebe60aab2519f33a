"""The masked flash kernels' share of the chip's bf16 peak, in %: the
floating-point operations they execute ON LIVE PAIRS in a step (the
configuration's ``attention_flops``: 18 D a live pair and query head,
forward, ``dq`` and ``dk/dv`` together; dead pairs a partial tile
computes are not counted, so the share cannot pass 100) over the device
seconds of ``flash_masked_fwd``, ``flash_masked_dq`` and
``flash_masked_dkv`` in a step, over the published peak.

The kernels' seconds are the trace's (``device_ops``, the whole traced
window), the steps in it the window over the median step.  None where one
of the three is under the trace's ten-operation cut, or the configuration
counts no such operations."""

KERNELS = ("flash_masked_fwd", "flash_masked_dq", "flash_masked_dkv")


def read(facts):
    trace, peaks = facts.get("trace"), facts.get("peaks")
    step_s = facts.get("step_s_median")
    count = getattr(facts.get("model"), "attention_flops", None)
    if not trace or not peaks or not step_s or count is None:
        return None
    seconds = {name.split(" ")[0]: s for name, s in trace["device_ops"]}
    if any(k not in seconds for k in KERNELS):
        return None
    steps = trace["window_s"] / step_s
    kernel_s = sum(seconds[k] for k in KERNELS) * facts["chips"]
    return 100.0 * count(facts["sizes"]) * facts["global_batch"] * steps / (
        kernel_s * facts["chips"] * peaks["bf16_flops_per_s"])
