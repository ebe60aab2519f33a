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

An operation that runs others (``while``, ``conditional``, ``call``) is an
event of the same ``XLA Ops`` line that CONTAINS their events, so the top
operations count nested time twice.  ``kernels`` and ``blocks`` count LEAF
time instead: a stretch of the line belongs to the innermost event over it.
``blocks`` puts each stretch under a block kind and a phase by the
operation's ``op_name`` in the step program: the stat ``tf_op`` of the
event's METADATA, which ``ProfileData`` does not expose.  It is read with
the ``xplane_pb2`` that ships inside the installed tensorflow wheel (loaded
by path; tensorflow itself is not imported).  Where that read fails, only
``blocks`` is lost: every other key is ``ProfileData``'s alone.
"""
import glob
import importlib.util
import os
import re
import sys
import sysconfig

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced_window"
SPAN_PREFIX = "bench."
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
PALLAS_TAG = " [pallas]"
TOP = 10
OP_NAME_STAT = "tf_op"
UNATTRIBUTED = "unattributed"
OPTIMIZER = "optimizer"
PHASES = (("transpose(", "backward"), ("jvp(", "forward"),
          (OPTIMIZER, OPTIMIZER))


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


def xplane_pb2():
    """The ``xplane_pb2`` module of the installed tensorflow wheel, loaded
    by path; None where there is none."""
    hits = glob.glob(os.path.join(
        sysconfig.get_paths()["purelib"], "tensorflow", "tsl", "profiler",
        "protobuf", "xplane_pb2.py"))
    if not hits:
        return None
    spec = importlib.util.spec_from_file_location("xplane_pb2", hits[0])
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _metadata_op_names(path):
    """``{chip: [op_name or None, ...]}`` for the ``XLA Ops`` events of
    every device plane, in the line's order, from the ``tf_op`` stat of
    each event's METADATA.  Raises where ``xplane_pb2`` is missing or
    cannot read the file."""
    pb = xplane_pb2()
    if pb is None:
        raise RuntimeError("no xplane_pb2 in the installed tensorflow")
    space = pb.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        op_stat = {k for k, s in plane.stat_metadata.items()
                   if s.name == OP_NAME_STAT}
        by_metadata = {key: next((s.str_value for s in meta.stats
                                  if s.metadata_id in op_stat
                                  and s.str_value), None)
                       for key, meta in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[int(m.group(1))] = [by_metadata.get(e.metadata_id)
                                        for e in line.events]
    return out


def join_op_names(devices, op_names):
    """``op_names`` where it has one per event of every chip's line in
    ``devices`` and at least one name; else None, with the reason on
    standard error.  ``blocks`` alone depends on it."""
    short = [chip for chip, events in devices.items()
             if len(op_names.get(chip, ())) != len(events)]
    if short:
        print("reduce_trace: no blocks: the XLA Ops lines of chips %s read "
              "differently by ProfileData and by xplane_pb2" % short,
              file=sys.stderr)
        return None
    if not any(n for names in op_names.values() for n in names):
        print("reduce_trace: no blocks: no event carries a tf_op",
              file=sys.stderr)
        return None
    return op_names


def load(path):
    """Read the xplane with ``jax.profiler.ProfileData`` into plain tuples
    ``(name, start_ns, duration_ns)``: the ``XLA Ops`` of every device
    plane, and the benchmark's spans from the host planes.  Beside them
    ``op_names``: each device event's ``op_name`` (None where it has none)
    from its metadata, joined by position (``join_op_names``); None where
    that cannot be read or joined."""
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
    op_names = None
    if devices:
        try:
            op_names = join_op_names(devices, _metadata_op_names(path))
        except Exception as e:  # noqa: BLE001 - blocks only; keep the rest
            print("reduce_trace: no blocks: %s: %s" % (type(e).__name__, e),
                  file=sys.stderr)
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1]),
            "op_names": op_names}


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


def phase_of(op_name):
    """``forward``, ``backward`` or ``optimizer`` by the step's outermost
    scope below the ``jit(...)`` ones: ``jvp(forward)``,
    ``transpose(jvp(forward))`` (a recomputing backward's forward
    included: it runs inside the transpose) or ``optimizer``; ``other``
    outside them (the step counter, the key's split)."""
    for part in op_name.split("/")[:-1]:
        if part.startswith("jit("):
            continue
        for mark, phase in PHASES:
            if part == mark or mark.endswith("(") and part.startswith(mark):
                return phase
        break
    return "other"


def kind_of(op_name, blocks):
    """The block kind of an operation: ``optimizer`` in that phase; else
    the kind whose pattern matches the OUTERMOST ``Block.name`` of the
    path that any pattern matches (``blocks``: kind -> compiled regular
    expressions, searched in each scope); ``unattributed`` where none
    does.  An operation outside every scope — a kernel the compiler made
    itself, such as ``ragged-dot-none:`` — is matched by its own name."""
    if phase_of(op_name) == OPTIMIZER:
        return OPTIMIZER
    parts = op_name.split("/")
    for part in parts[:-1] or parts:
        for kind, patterns in blocks.items():
            if any(p.search(part) for p in patterns):
                return kind
    return UNATTRIBUTED


def leaf_times(intervals):
    """``[(start, end), ...]`` of one line -> the leaf time of each: every
    stretch of the line belongs to the innermost interval over it (of
    those that cover it, the one that started last; of two that start
    together, the shorter).  The leaf times add up to the union's length,
    so nested time is counted once."""
    n = len(intervals)
    order = sorted(range(n), key=lambda i: (intervals[i][0],
                                            -intervals[i][1]))
    bounds = sorted({t for pair in intervals for t in pair})
    leaf, stack, j = [0.0] * n, [], 0
    for a, b in zip(bounds, bounds[1:]):
        while j < n and intervals[order[j]][0] <= a:
            stack.append(order[j])
            j += 1
        while stack and intervals[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            leaf[stack[-1]] += b - a
    return leaf


def reduce(loaded, blocks=None):
    """The loaded trace -> ``{"busy_s", "window_s", "device_ops",
    "idle_gaps", "pallas_s", "chips", "kernels", "blocks"}``.  Times in
    seconds; ``busy_s`` and ``pallas_s`` are means over the chips,
    ``device_ops`` and ``idle_gaps`` are ``[name, seconds]`` pairs, largest
    first, at most ten each (the gaps of one host span are added up).

    ``kernels``: every ``tpu_custom_call`` — the Pallas kernels and the
    compiler's own Mosaic kernels, such as ``ragged-dot-none`` — as
    ``[name, seconds, calls]``, largest first, with no cut; the name
    without the `` [pallas]`` tag, seconds and calls means over the chips,
    a call that the window's edge cuts counted by the share of it inside.
    ``blocks``: ``{kind: {phase: seconds}}`` of LEAF time (``leaf_times``),
    means over the chips, the kind by ``kind_of`` with ``blocks`` (kind ->
    regular expressions; None: no kind but ``optimizer``), the phase by
    ``phase_of``; an operation with no ``op_name`` is ``unattributed`` in
    phase ``other``.  The kinds add up to ``busy_s``.  ``blocks`` is None
    where the trace carries no ``op_name``.  Returns None for a trace in
    which no operation ran on a device."""
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
    all_names = loaded.get("op_names")
    patterns = {kind: [re.compile(p) for p in found]
                for kind, found in (blocks or {}).items()}
    kinds = {}                 # op_name -> (kind, phase)
    busy, pallas, ops, gaps = [], [], {}, {}
    kernels, by_block = {}, {} if all_names is not None else None
    for chip, events in devices.items():
        inside = [i for i, (n, s, d) in enumerate(events)
                  if s + d > w0 and s < w1]
        clipped = [(events[i][0], max(events[i][1], w0),
                    min(events[i][1] + events[i][2], w1)) for i in inside]
        merged = _union((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in merged))
        kernel = 0.0
        for i, (name, s, e) in zip(inside, clipped):
            op = op_name(name)
            ops[op] = ops.get(op, 0.0) + (e - s) / len(devices)
            if op.endswith(PALLAS_TAG):
                kernel += e - s
                bare, full = op[:-len(PALLAS_TAG)], events[i][2]
                seconds, calls = kernels.get(bare, (0.0, 0.0))
                kernels[bare] = (seconds + (e - s) / len(devices),
                                 calls + ((e - s) / full if full else 1.0)
                                 / len(devices))
        pallas.append(kernel)
        if by_block is not None:
            names = all_names.get(chip) or [None] * len(events)
            for i, t in zip(inside, leaf_times([(s, e) for _, s, e
                                                in clipped])):
                name = names[i]
                if name is None:
                    key = (UNATTRIBUTED, "other")
                else:
                    key = kinds.get(name)
                    if key is None:
                        key = kinds[name] = (kind_of(name, patterns),
                                             phase_of(name))
                phases = by_block.setdefault(key[0], {})
                phases[key[1]] = phases.get(key[1], 0.0) \
                    + t * 1e-9 / len(devices)
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
            "chips": len(devices),
            "kernels": [[n, s * 1e-9, c] for n, (s, c) in sorted(
                kernels.items(), key=lambda kv: -kv[1][0])],
            "blocks": by_block}
