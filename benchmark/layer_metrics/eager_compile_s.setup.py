"""Seconds jax spent tracing, lowering and compiling (or reloading from the
persistent cache) the programs of ``eager_programs.setup``: every owner of
the program's ``compile_totals()`` but ``parallel.step.call``.  Like that
count it INCLUDES the benchmark's own reference check (an op-by-op forward
outside every span, most of the seconds in a cold run), and the programs
compiled inside ``gluon.param.place`` and ``parallel.state_init`` lie in
``param_build_s.setup`` as well.  None where the run has no steps or the
program books no compile events."""
STEP_OWNER = "parallel.step.call"


def read(facts):
    try:
        from mxnet_tpu.telemetry import compile_totals
    except ImportError:
        return None
    totals = compile_totals()
    if not facts.get("steps") or not totals:
        return None
    return sum(total["trace_s"] + total["lower_s"] + total["backend_s"]
               for owner, total in totals.items()
               if owner != STEP_OWNER)
