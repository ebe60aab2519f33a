"""Share of the programs looked up in jax's persistent compilation cache
that were found there, in %: ``100 * cache_hits / (cache_hits +
cache_misses)`` over every owner of the program's ``compile_totals()``.
Near 100 in a warm process; what is under it is what the machine's cache
had evicted (or never held): the number that tells a cold ``setup_s`` from
a grown one.  None where the run has no steps, the program books no compile
events, or nothing was looked up (the cache is off)."""


def read(facts):
    try:
        from mxnet_tpu.telemetry import compile_totals
    except ImportError:
        return None
    totals = compile_totals().values()
    hits = sum(total["cache_hits"] for total in totals)
    looked_up = hits + sum(total["cache_misses"] for total in totals)
    if not facts.get("steps") or not looked_up:
        return None
    return 100.0 * hits / looked_up
