"""Programs jax handed its backend outside the step's jitted call: every
owner of the program's ``compile_totals()`` but ``parallel.step.call`` —
the model's build, optimizer state, the step's small host-side operands,
AND the benchmark's own reference check, whose op-by-op forward
(``benchmark/configs/*/model.py``) runs in this process outside every span
and so reads as ``eager`` beside the model's own.  Most of the count is
that check: the metric's level is the harness's as much as the program's,
and a build that has grown cannot be told here from a check that has
(``PERF.md`` §7 asks the next ``benchmark`` issue for the split).  None
where the run has no steps or the program books no compile events."""
STEP_OWNER = "parallel.step.call"


def read(facts):
    try:
        from mxnet_tpu.telemetry import compile_totals
    except ImportError:
        return None
    totals = compile_totals()
    if not facts.get("steps") or not totals:
        return None
    return sum(total["programs"] for owner, total in totals.items()
               if owner != STEP_OWNER)
