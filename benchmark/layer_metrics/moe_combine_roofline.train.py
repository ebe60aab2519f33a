"""The ``moe_combine`` kernel's share of the chip's HBM bandwidth, in %: the
bytes its calls have to move (the configuration's ``moe_combine_bytes``: a
bf16 row of D for each route to a held expert a layer has — the blocks'
state at the last step, the layers' mean — and the (N, D) bf16 result) times
its calls in the traced window, over its device seconds there, over the
published bandwidth.  The pass that packs the slots' table and the reads of
row 0 for the routes with no slot here are not useful bytes, so the share
cannot pass 100.  None untraced, where the kernel did not run, or where the
program has no experts."""
from benchmark import device_time


def read(facts):
    count = getattr(facts.get("model"), "moe_combine_bytes", None)
    timed = device_time.kernel_time(facts, ("moe_combine",))
    if count is None or timed is None or not facts.get("peaks"):
        return None
    routes = device_time.held_routes()
    if not routes:
        return None
    seconds, calls = timed
    rows = facts["global_batch"] / facts["chips"]
    return device_time.share(
        count(facts["sizes"], rows, sum(routes) / len(routes)) * calls,
        seconds, facts["peaks"]["hbm_bytes_per_s"])
