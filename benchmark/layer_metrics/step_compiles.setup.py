"""Times jax handed a step program to its backend (compiled, or reloaded
from the persistent cache) since the process started: the program's
recompile detector, ``compile_counts()`` summed over ``DataParallelStep[*]``,
which since PR 35 counts jax's own recompiles of a cached step too.  2 in
every cell while the second call arrives with other committed leaves than
the first; 1 is ROADMAP S5's target.

None where the run has no steps or the program does not book jax's compile
events (no ``compile_totals``): its detector then saw its own misses only."""


def read(facts):
    try:
        from mxnet_tpu.telemetry import compile_counts, compile_totals
    except ImportError:
        return None
    if not facts.get("steps") or not compile_totals():
        return None
    return sum(n for name, n in compile_counts().items()
               if name.startswith("DataParallelStep["))
