"""The causal flash kernels' share of the chip's bf16 peak, in %: the
operations they execute ON LIVE PAIRS in a step (the configuration's
``causal_attention_flops``: ``S (S + 1) / 2`` pairs a query head and layer
that runs them, ``18 D`` a pair over forward, ``dq`` and ``dk/dv``; the
pairs above the diagonal that a tile computes are not counted, so the share
cannot pass 100) over the device seconds a step of ``flash_stream_fwd`` +
``flash_dq`` + ``flash_dkv``, over the published peak.  None untraced,
where one of the three did not run, or where the configuration counts no
such operations."""
from benchmark import device_time

KERNELS = ("flash_stream_fwd", "flash_dq", "flash_dkv")


def read(facts):
    count = getattr(facts.get("model"), "causal_attention_flops", None)
    steps = device_time.steps_in_window(facts)
    timed = device_time.kernel_time(facts, KERNELS)
    if count is None or not steps or timed is None \
            or not facts.get("peaks"):
        return None
    return device_time.share(
        count(facts["sizes"]) * facts["global_batch"] * steps,
        timed[0] * facts["chips"], facts["peaks"]["bf16_flops_per_s"])
