"""The chunked delta rule's share of the chip's bf16 peak, in %: the rule's
products in a step (the configuration's ``delta_scan_flops`` at the
published chunk, forward and backward, no recomputation) over the leaf
device seconds a step of the kind ``delta_scan`` (the rule's scopes
``delta.*``: its products, solves, exponentials and the scan's carry), over
the published peak.  None untraced, where the kind has no time, or where
the configuration counts no delta rule."""
from benchmark import device_time


def read(facts):
    count = getattr(facts.get("model"), "delta_scan_flops", None)
    ms = device_time.block_ms(facts, "delta_scan")
    if count is None or ms is None or not facts.get("peaks"):
        return None
    return device_time.share(count(facts["sizes"]) * facts["global_batch"],
                             ms * 1e-3 * facts["chips"],
                             facts["peaks"]["bf16_flops_per_s"])
