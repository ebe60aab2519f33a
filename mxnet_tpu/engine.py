"""Engine control facade (reference ``python/mxnet/engine.py`` bulk
context + the ``MXNET_ENGINE_TYPE`` env knob, ``src/engine/engine.cc:32``).

There is no hand-built dependency engine to control — JAX async dispatch +
XLA scheduling replace it (SURVEY.md §7).  What remains meaningful:

* ``NaiveEngine`` debugging semantics (run everything synchronously,
  one op at a time) maps to ``jax.disable_jit`` — same observable effect:
  per-op eager execution, python-level stack traces at the failing op.
  Honored both via ``MXNET_ENGINE_TYPE=NaiveEngine`` at import and the
  ``naive_engine()`` context manager.
* ``bulk``/``set_bulk_size`` (op batching to cut engine overhead,
  ``MXNET_ENGINE_BULK_SIZE``) are accepted no-ops: XLA fuses whole jitted
  programs, which is strictly stronger than engine bulking.
"""
from __future__ import annotations

import contextlib
import os

__all__ = ["bulk", "set_bulk_size", "naive_engine", "engine_type",
           "enable_compilation_cache"]

_BULK_SIZE = int(os.environ.get("MXNET_ENGINE_BULK_SIZE", 15))


def engine_type() -> str:
    """Active engine semantics ('ThreadedEnginePerDevice' = normal async
    jax dispatch, 'NaiveEngine' = jit disabled)."""
    import jax
    if jax.config.jax_disable_jit:
        return "NaiveEngine"
    return os.environ.get("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice")


@contextlib.contextmanager
def naive_engine():
    """Synchronous per-op execution for debugging (reference NaiveEngine,
    src/engine/naive_engine.cc) — wraps ``jax.disable_jit``."""
    import jax
    with jax.disable_jit():
        yield


def set_bulk_size(size):
    """(reference engine.py set_bulk_size) — returns the previous size;
    a no-op for execution since XLA fuses jitted programs wholesale."""
    global _BULK_SIZE
    prev, _BULK_SIZE = _BULK_SIZE, int(size)
    return prev


@contextlib.contextmanager
def bulk(size):
    """(reference engine.py bulk) — op-batching hint; XLA fusion subsumes
    it, so this only scopes the bookkeeping value."""
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)


def _apply_env_engine_type():
    """Honor MXNET_ENGINE_TYPE=NaiveEngine at import (reference
    src/engine/engine.cc:32-45 reads it at singleton creation)."""
    if os.environ.get("MXNET_ENGINE_TYPE") == "NaiveEngine":
        import jax
        jax.config.update("jax_disable_jit", True)


_apply_env_engine_type()


def enable_compilation_cache():
    """Persistent XLA executable cache (the TPU analogue of the
    reference's cuDNN autotune cache + graph-plan reuse): compiled
    programs are keyed by HLO and reused across PROCESSES, so repeat
    runs of benches/tests/training scripts skip their multi-second
    compiles.  Safe to call multiple times; returns the directory in
    use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already holds that
    directory and no other is set here; otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` (the path is part of the cache key,
    so a directory that moves never hits).  A directory that cannot be
    created raises: a run that silently recompiles everything is not
    the run that was asked for."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
