"""The routed experts' products' share of the chip's bf16 peak, in %: the
useful operations of a step's products (the configuration's
``moe_product_flops`` of the routes to held experts at the last step — the
blocks' state, as ``moe_local_token_share.train`` reads it — ``2 D F`` a
product and route, forward, input and weight gradient) over the device
seconds a step of the kernels that ran them — ``moe_blocks_fwd`` +
``moe_blocks_dx`` + ``moe_blocks_dw``, or the compiler's
``ragged-dot-none``, whichever ran (both where both did) — over the
published peak.  The same work is counted whatever implements it; a
recomputing backward's second forward and a block's empty slots are not
work, so the share cannot pass 100.  None untraced, where neither set of
kernels ran, or where the program has no experts."""
from benchmark import device_time

KERNELS = (("moe_blocks_fwd", "moe_blocks_dx", "moe_blocks_dw"),
           ("ragged-dot-none",))


def read(facts):
    count = getattr(facts.get("model"), "moe_product_flops", None)
    steps = device_time.steps_in_window(facts)
    if count is None or not steps or not facts.get("peaks"):
        return None
    timed = [device_time.kernel_time(facts, names) for names in KERNELS]
    seconds = sum(t[0] for t in timed if t) * facts["chips"]
    routes = device_time.held_routes() if seconds else None
    if not routes:
        return None
    return device_time.share(count(facts["sizes"], sum(routes)) * steps,
                             seconds, facts["peaks"]["bf16_flops_per_s"])
