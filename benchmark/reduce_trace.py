"""From a profiler trace (``*.xplane.pb``) to the numbers the benchmark
prints: device busy time, the traced window, the device operations that
took most time, the Pallas kernels' share, and the longest idle gaps named
by what the host was doing.

What the trace of a TPU v5e looks like (jax 0.9.0, looked at by hand in
PR 22/23): one plane ``/device:TPU:<n>`` per chip with the lines ``Steps``,
``XLA Modules``, ``XLA Ops`` and ``Async XLA Ops``; an event of ``XLA Ops``
is named by its WHOLE HLO line (``%fusion.12 = bf16[...] fusion(...)``), a
Pallas kernel is a ``custom-call`` whose line says
``custom_call_target="tpu_custom_call"``.  Host threads are lines of the
plane ``/host:CPU``; the benchmark's own spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``) are events there, on the same clock.

Busy time is the union of the ``XLA Ops`` intervals (what the TensorCore
executed; the asynchronous copies of ``Async XLA Ops`` overlap them and are
not compute), clipped to the traced window and averaged over the chips.
The traced window is the host span ``bench.traced_window`` where the driver
wrote one, and the span from the first to the last device operation
otherwise.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced_window"
SPAN_PREFIX = "bench."
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
PALLAS_TAG = " [pallas]"
TOP = 10


def find_xplane(trace_dir):
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if len(found) != 1:
        raise RuntimeError("expected one xplane under %s, found %r"
                           % (trace_dir, found))
    return found[0]


def op_name(hlo_line):
    """``%fusion.398 = (bf16[64,512,3072]...) fusion(...)`` -> ``fusion``.

    The event name is the whole HLO line: the operation is what stands
    before `` = ``, without the ``%`` and the numbering XLA appends.  A
    Pallas kernel keeps its jaxpr name (``jvp__``, ``transpose_jvp___``
    until the program names its kernels) and is tagged `` [pallas]``."""
    head = hlo_line.split(" = ", 1)[0].strip().lstrip("%")
    head = re.sub(r"(\.\d+)+$", "", head)
    return head + (PALLAS_TAG if PALLAS_MARK in hlo_line else "")


def load(path):
    """Read the xplane with ``jax.profiler.ProfileData`` into plain tuples
    ``(name, start_ns, duration_ns)``: the ``XLA Ops`` of every device
    plane, and the benchmark's spans from the host planes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.duration_ns)))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def _union(intervals):
    """Merge ``(start, end)`` pairs; returns the merged list, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _host_span_at(spans, t):
    """The innermost benchmark span that covers time ``t``."""
    covering = [(dur, name) for name, start, dur in spans
                if name != WINDOW_SPAN and start <= t < start + dur]
    return min(covering)[1] if covering else "host.unmarked"


def reduce(loaded):
    """The loaded trace -> ``{"busy_s", "window_s", "device_ops",
    "idle_gaps", "pallas_s", "chips"}``.  Times in seconds; ``busy_s`` and
    ``pallas_s`` are means over the chips, ``device_ops`` and ``idle_gaps``
    are ``[name, seconds]`` pairs, largest first, at most ten each (the
    gaps of one host span are added up).  Returns None for a trace in which
    no operation ran on a device."""
    devices = {k: v for k, v in loaded["devices"].items() if v}
    if not devices:
        return None
    spans = loaded["spans"]
    window = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN]
    if window:
        w0, w1 = window[0]
    else:
        w0 = min(s for ev in devices.values() for _, s, _ in ev)
        w1 = max(s + d for ev in devices.values() for _, s, d in ev)
    busy, pallas, ops, gaps = [], [], {}, {}
    for events in devices.values():
        clipped = [(n, max(s, w0), min(s + d, w1)) for n, s, d in events
                   if s + d > w0 and s < w1]
        merged = _union((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in merged))
        kernel = 0.0
        for name, s, e in clipped:
            op = op_name(name)
            ops[op] = ops.get(op, 0.0) + (e - s) / len(devices)
            if op.endswith(PALLAS_TAG):
                kernel += e - s
        pallas.append(kernel)
        edges = [w0] + [t for pair in merged for t in pair] + [w1]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                who = _host_span_at(spans, edges[i])
                gaps[who] = gaps.get(who, 0.0) \
                    + (edges[i + 1] - edges[i]) / len(devices)

    def top(table):
        return [[n, s * 1e-9] for n, s in
                sorted(table.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (w1 - w0) * 1e-9,
            "pallas_s": sum(pallas) / len(pallas) * 1e-9,
            "device_ops": top(ops), "idle_gaps": top(gaps),
            "chips": len(devices)}
