"""Seconds jax spent tracing, lowering and compiling (or reloading from the
persistent cache) inside the step's jitted call: the program's
``compile_totals()`` of the owner ``parallel.step.call`` — the step program
as often as it compiled, and what its trace compiled on the way.  (The
benchmark's own ``memory_analysis()`` compile of the step is served from
jax's in-memory cache and reaches no backend: it books nothing.)  None
where the run has no steps or the program books no compile events."""


def read(facts):
    try:
        from mxnet_tpu.telemetry import compile_totals
    except ImportError:
        return None
    total = compile_totals().get("parallel.step.call")
    if not facts.get("steps") or total is None:
        return None
    return total["trace_s"] + total["lower_s"] + total["backend_s"]
