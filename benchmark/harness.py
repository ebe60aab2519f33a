"""What every driver measures with: the benchmark's own spans, the count of
compilations, the profiler around a short steady window, and the device's
memory.  One ``Run`` per process; the driver gets it as its only argument.
"""
import contextlib
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time

TRACE_SECONDS = 2.5       # the traced part of a --trace 1 window


def load_module(path, name):
    """Import the file at ``path`` (names of cells, metrics and
    configurations are not Python identifiers, so files are found by path,
    not by import name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def p95(values):
    """Nearest-rank 95th percentile."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(0.95 * len(ordered)) - 1)]


class CompileCounter:
    """Every program jax hands its backend, compiled or reloaded from the
    persistent cache, from jax's own monitoring events (as
    ``chip_smoke._count_compiles``)."""

    def __init__(self):
        import jax.monitoring

        self.counts = {"programs": 0, "seconds": 0.0, "cache_hits": 0,
                       "cache_misses": 0}

        def on_duration(event, seconds, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.counts["programs"] += 1
                self.counts["seconds"] += seconds

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.counts["cache_hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.counts["cache_misses"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return dict(self.counts)

    def since(self, before):
        return {k: self.counts[k] - before[k] for k in self.counts}


class Run:
    """The state of one benchmark run, handed to the driver.

    The driver calls ``window_opens()`` when set-up is over (everything
    before it is ``setup_s``), wraps its calls into the system in
    ``span(name)``, and — in a traced run — brackets a short steady part
    of its window with ``trace_start()`` / ``trace_stop()``."""

    def __init__(self, t0, sizes, traffic, model, seed, seconds, traced,
                 devices):
        self.t0 = t0
        self.sizes, self.traffic, self.model = sizes, traffic, model
        self.seed, self.seconds = seed, seconds
        self.traced, self.devices = traced, devices
        self.trace_seconds = TRACE_SECONDS
        self.compiles = CompileCounter()
        self.spans = {}            # name -> [seconds], inside the window
        self.setup_s = None
        self.compiles_at_open = None
        self.trace = None          # the reduced trace of a traced run
        self._trace_dir = None
        self._window_span = None

    # -- progress lines (stdout, before the last line) ---------------------
    def say(self, phase, **fields):
        print("[%s] %s" % (phase, json.dumps(fields, sort_keys=True,
                                             default=str)), flush=True)

    # -- set-up / window ---------------------------------------------------
    def window_opens(self):
        self.setup_s = time.perf_counter() - self.t0
        self.compiles_at_open = self.compiles.snapshot()
        self.spans.clear()

    def compiles_in_window(self):
        return self.compiles.since(self.compiles_at_open)["programs"]

    @contextlib.contextmanager
    def span(self, name):
        """A host span of the benchmark's own: kept for the per-layer
        readers and written into the profiler's trace where one runs."""
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t)

    # -- profiler ----------------------------------------------------------
    def trace_start(self):
        import jax

        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the benchmark's spans only
        options.host_tracer_level = 2
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        self._window_span = jax.profiler.TraceAnnotation(
            "bench.traced_window")
        self._window_span.__enter__()

    def trace_stop(self):
        """Call when the last traced work has completed and before
        anything is shut down: the traced window ends here."""
        import jax
        from benchmark import reduce_trace

        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            path = reduce_trace.find_xplane(self._trace_dir)
            keep = os.environ.get("BENCH_KEEP_XPLANE")
            if keep:
                os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
                shutil.copyfile(path, keep)
            self.trace = reduce_trace.reduce(reduce_trace.load(path))
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    # -- device memory -----------------------------------------------------
    def memory_peak_bytes(self):
        """Peak on the fullest chip: what was live between programs
        (``peak_bytes_in_use``) plus what the runtime reserved for the
        programs' own temporaries (``peak_bytes_reserved``), which the
        first does not count (PR 21: 0.33 GB "in use" under a step with
        7.4 GB of temporaries; PR 23: 7.39 GB reserved for that step)."""
        stats = [d.memory_stats() or {} for d in self.devices]
        return int(max(s.get("peak_bytes_in_use", 0)
                       + s.get("peak_bytes_reserved", 0) for s in stats))
