#!/usr/bin/env python
"""Parse training logs into a table (reference ``tools/parse_log.py``).

Consumes the log lines the Module/callback stack emits::

    INFO:root:Epoch[3] Train-accuracy=0.96
    INFO:root:Epoch[3] Time cost=2.3
    INFO:root:Epoch[3] Validation-accuracy=0.94

the telemetry-enriched Speedometer line::

    INFO:root:Epoch[3] Batch [50-100]\tSpeed: 1234.56 samples/sec\t\
step-ms=12.345\tring=3/4\taccuracy=0.912000

and (``--jsonl``) the telemetry JSONL metrics sink
(``mxnet_tpu.telemetry.export_jsonl`` / ``set_jsonl_sink``), and prints
markdown (or tsv) with one row per epoch.

``--jsonl --trace <id>`` renders ONE trace as a waterfall table: every
span/event carrying that trace id, ordered by timestamp, nested by the
``sid``/``parent`` chain — one serve request or one training step end
to end, across ranks when the input is a collector-merged export.

``--incident <dir>`` summarises a flight-recorder bundle
(``mxnet_tpu.flight_recorder.dump_incident``): the trigger, the
journal-tail census, histogram quantiles and counters at the moment of
death.

``--lint`` renders a graftlint JSON findings report
(``python -m tools.lint --format json``) as a per-rule/per-file table
plus the individual new findings — the human-readable face of the lint
gate's machine output.
"""
import argparse
import json
import math
import os
import re
import sys

TRAIN_RE = re.compile(r"Epoch\[(\d+)\] Train-([\w-]+)=([\d.eE+-]+)")
VAL_RE = re.compile(r"Epoch\[(\d+)\] Validation-([\w-]+)=([\d.eE+-]+)")
TIME_RE = re.compile(r"Epoch\[(\d+)\] Time cost=([\d.eE+-]+)")
SPEED_RE = re.compile(r"Epoch\[(\d+)\].*Speed: ([\d.eE+-]+) samples/sec")
STEPMS_RE = re.compile(r"Epoch\[(\d+)\].*\bstep-ms=([\d.eE+-]+)")
RING_RE = re.compile(r"Epoch\[(\d+)\].*\bring=(\d+)/(\d+)")


def parse(lines):
    """rows[epoch] = {"train": {metric: v}, "val": {metric: v},
    "time": float|None, "speed": [..], "step_ms": [..], "ring": [..]} —
    every metric name kept (fit can emit several eval metrics per
    epoch); step_ms/ring come from the telemetry-enriched Speedometer
    line."""
    rows = {}

    def row(e):
        return rows.setdefault(int(e), {"train": {}, "val": {},
                                        "time": None, "speed": [],
                                        "step_ms": [], "ring": []})
    for line in lines:
        m = TRAIN_RE.search(line)
        if m:
            row(m.group(1))["train"][m.group(2)] = float(m.group(3))
        m = VAL_RE.search(line)
        if m:
            row(m.group(1))["val"][m.group(2)] = float(m.group(3))
        m = TIME_RE.search(line)
        if m:
            row(m.group(1))["time"] = float(m.group(2))
        m = SPEED_RE.search(line)
        if m:
            row(m.group(1))["speed"].append(float(m.group(2)))
        m = STEPMS_RE.search(line)
        if m:
            row(m.group(1))["step_ms"].append(float(m.group(2)))
        m = RING_RE.search(line)
        if m:
            row(m.group(1))["ring"].append(
                int(m.group(2)) / max(1, int(m.group(3))))
    return rows


def _hist_merge(into, d):
    """Merge one ``Histogram.to_dict`` snapshot into ``into`` (same
    sparse-bucket form) — how multi-rank snapshot records in one
    collector-merged file combine.  Pure dict math: this script stays
    import-free of mxnet_tpu, and the geometry (``lo``/``bpd``) rides
    in the snapshot itself."""
    if into is None:
        return dict(d, buckets=dict(d.get("buckets") or {}))
    into["count"] = into.get("count", 0) + d.get("count", 0)
    into["sum"] = into.get("sum", 0.0) + d.get("sum", 0.0)
    for k in ("min", "max"):
        pick = min if k == "min" else max
        vs = [v for v in (into.get(k), d.get(k)) if v is not None]
        into[k] = pick(vs) if vs else None
    b = into.setdefault("buckets", {})
    for i, c in (d.get("buckets") or {}).items():
        b[i] = b.get(i, 0) + c
    return into


def _hist_quantile(d, q):
    """Quantile from a ``Histogram.to_dict`` snapshot: geometric
    midpoint of the bucket holding the q-th observation, clamped by the
    exact min/max (mirrors mxnet_tpu.telemetry.Histogram.quantile)."""
    count = d.get("count", 0)
    if not count:
        return None
    lo = float(d.get("lo", 1e-3))
    bpd = float(d.get("bpd", 10))

    def edge(j):
        return lo * 10.0 ** (j / bpd)

    target = q * count
    seen = 0
    for i, c in sorted((int(k), v)
                       for k, v in (d.get("buckets") or {}).items()):
        seen += c
        if seen >= target and c:
            b_lo = 0.0 if i == 0 else edge(i - 1)
            b_hi = edge(i)
            mid = math.sqrt(b_lo * b_hi) if b_lo > 0 else b_hi / 2.0
            if d.get("min") is not None:
                mid = max(d["min"], mid)
            if d.get("max") is not None:
                mid = min(d["max"], mid)
            return mid
    return d.get("max")


def parse_jsonl(lines):
    """Parse a telemetry JSONL sink (one JSON object per line) into
    ``{"spans": {name: {count, mean_ms, total_ms}}, "counters": {...},
    "gauges": {...}, "recompiles": [...], "steps": int}`` plus the
    observability streams: ``histograms`` (name -> merged
    ``Histogram.to_dict``), ``traces`` (trace id -> its records, in
    file order) and ``incidents`` (flight-recorder dump journal).

    Span stats are aggregated from the per-event ``dur_ms`` stream; a
    trailing ``kind="snapshot"`` record (written by ``export_jsonl``)
    overrides counters/gauges with the authoritative final values —
    histogram snapshots from SEVERAL ranks' records merge by adding
    counts."""
    spans = {}
    counters = {}
    gauges = {}
    recompiles = []
    hbm = {}
    lockorder = []
    numerics = {}
    histograms = {}
    traces = {}
    incidents = []
    elastic = []
    compress = []
    serve = {"events": {}, "batches": 0, "fill_pct_sum": 0.0,
             "queue_depth_sum": 0, "wait_ms_sum": 0.0, "states": []}
    lint_gate = None
    chaos_audit = None
    steps = 0
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        kind = rec.get("kind")
        if rec.get("trace") is not None:
            traces.setdefault(str(rec["trace"]), []).append(rec)
        if kind == "span":
            s = spans.setdefault(rec["name"], {"count": 0, "total_ms": 0.0})
            s["count"] += 1
            s["total_ms"] += float(rec.get("dur_ms", 0.0))
        elif kind == "incident":
            # flight-recorder dump journal: one row per committed /
            # capped / failed bundle (mxnet_tpu.flight_recorder)
            incidents.append({"event": rec.get("name"),
                              "reason": rec.get("reason"),
                              "path": rec.get("path"),
                              "error": rec.get("error")})
        elif kind == "step":
            steps += 1
        elif kind == "recompile":
            recompiles.append({"name": rec.get("name"),
                               "n": rec.get("n"),
                               "changed": rec.get("changed", [])})
        elif kind == "hbm":
            # static per-chip HBM estimate, one per compiled program
            # (mxnet_tpu.parallel journals these at jit-cache misses);
            # keyed (program, mode) so the scan and per-call variants of
            # one step each keep their row
            key = "%s/%s" % (rec.get("program", "?"),
                             rec.get("mode", "?"))
            hbm[key] = rec
        elif kind == "lockorder":
            # runtime lock-order sanitizer observations (one event per
            # newly observed acquisition edge — tools.lint.runtime_lockorder)
            lockorder.append({"src": rec.get("src"),
                              "dst": rec.get("dst")})
        elif kind == "numerics":
            # runtime numerics sanitizer observations (one event per
            # leaf first-sighting / dtype change / non-finite count —
            # tools.lint.runtime_numerics, Monitor nan_guard)
            leaf = rec.get("leaf", "?")
            n = numerics.setdefault(leaf, {"dtypes": [], "nonfinite": 0,
                                           "size": rec.get("size"),
                                           "first_bad_step": None})
            dt = rec.get("dtype")
            if dt and dt not in n["dtypes"]:
                n["dtypes"].append(dt)
            bad = int(rec.get("nonfinite") or 0)
            n["nonfinite"] += bad
            if bad and n["first_bad_step"] is None:
                n["first_bad_step"] = rec.get("step")
        elif kind == "compress":
            # compressed-collective decisions (parallel/compression.py
            # wire, journaled by DataParallelStep / Trainer at each
            # grad_compression resolution): one row per decision with
            # the schedule-arithmetic wire bytes vs the f32 baseline
            if rec.get("name") == "decision":
                compress.append(
                    {k: rec.get(k) for k in
                     ("mode", "requested", "path", "dp", "params",
                      "dtype", "wire_bytes", "scale_bytes", "f32_bytes",
                      "ratio")})
        elif kind in ("elastic", "ckpt"):
            # elastic-transition / checkpoint journal events (one per
            # detect/reshard/write/restore — mxnet_tpu.parallel.elastic
            # + mxnet_tpu.checkpoint): the recovery-protocol census
            w_from, w_to = rec.get("world_from"), rec.get("world_to")
            if w_from is not None and w_to is not None \
                    and w_from != w_to:
                world = "%s->%s" % (w_from, w_to)
            elif w_to is not None or w_from is not None:
                world = str(w_to if w_to is not None else w_from)
            else:
                world = rec.get("world")
                world = str(world) if world is not None else None
            elastic.append({"event": "%s/%s" % (kind, rec.get("name")),
                            "step": rec.get("step"),
                            "world": world,
                            "bytes": rec.get("bytes"),
                            "dur_ms": rec.get("dur_ms"),
                            "detail": rec.get("change") or rec.get("reason")
                            or rec.get("error")})
        elif kind == "serve":
            # serving-stack journal events (mxnet_tpu.serve.server):
            # per-batch fill/queue-depth stream plus one row per
            # shed/timeout/reject/watchdog/quarantine/state transition
            name = rec.get("name", "?")
            serve["events"][name] = serve["events"].get(name, 0) + 1
            if name == "batch":
                serve["batches"] += 1
                serve["fill_pct_sum"] += float(rec.get("fill_pct") or 0.0)
                serve["queue_depth_sum"] += int(
                    rec.get("queue_depth") or 0)
                serve["wait_ms_sum"] += float(rec.get("wait_ms") or 0.0)
            elif name == "state":
                serve["states"].append(
                    "%s->%s" % (rec.get("state_from"),
                                rec.get("state_to")))
        elif kind == "lint" and rec.get("name") == "gate":
            lint_gate = rec
        elif kind == "lint" and rec.get("name") == "chaos_audit":
            # fault-injection coverage matrix (tools.lint --audit-chaos
            # --telemetry): one row per fault point
            chaos_audit = rec
        elif kind == "snapshot":
            counters.update(rec.get("counters", {}))
            gauges.update(rec.get("gauges", {}))
            for name, agg in rec.get("spans", {}).items():
                spans[name] = {"count": agg["count"],
                               "total_ms": agg["total_ms"]}
            for name, h in (rec.get("histograms") or {}).items():
                histograms[name] = _hist_merge(histograms.get(name), h)
    for s in spans.values():
        s["mean_ms"] = round(s["total_ms"] / s["count"], 4) \
            if s["count"] else None
        s["total_ms"] = round(s["total_ms"], 4)
    return {"spans": spans, "counters": counters, "gauges": gauges,
            "recompiles": recompiles, "steps": steps, "hbm": hbm,
            "lockorder": lockorder, "numerics": numerics,
            "elastic": elastic, "compress": compress, "serve": serve,
            "lint_gate": lint_gate,
            "chaos_audit": chaos_audit, "histograms": histograms,
            "traces": traces, "incidents": incidents}


def _render_hbm(hbm, fmt="markdown"):
    """Bytes-per-chip table, one row per compiled program, from the
    hbm/estimate journal events."""
    if not hbm:
        return []
    header = ["program", "mode", "params-MiB", "state-MiB", "act-MiB",
              "total-MiB", "shards"]
    out = ["", "static HBM estimate (bytes/chip per compiled program):"]
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")

    def mib(rec, key):
        v = rec.get(key)
        return "%.4g" % (float(v) / 1048576.0) if v is not None else "-"

    for key in sorted(hbm):
        r = hbm[key]
        vals = [str(r.get("program", "?")), str(r.get("mode", "?")),
                mib(r, "params_bytes_per_chip"),
                mib(r, "opt_state_bytes_per_chip"),
                mib(r, "activation_bytes_per_chip"),
                mib(r, "total_bytes_per_chip"),
                str(r.get("n_shards", "-"))]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    return out


def render_jsonl(agg, fmt="markdown"):
    """One row per span name, then counters — the epoch-table analogue
    for the metrics sink."""
    header = ["span", "count", "mean-ms", "total-ms"]
    out = []
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")
    for name in sorted(agg["spans"]):
        s = agg["spans"][name]
        vals = [name, str(s["count"]), "%.6g" % (s["mean_ms"] or 0),
                "%.6g" % s["total_ms"]]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    for name in sorted(agg["counters"]):
        vals = ["counter:" + name, "%.6g" % agg["counters"][name], "-", "-"]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    if agg["recompiles"]:
        out.append("")
        out.append("recompiles:")
        for r in agg["recompiles"]:
            out.append("  %s (#%s): %s" % (r["name"], r["n"],
                                           "; ".join(r["changed"])))
    if agg.get("lockorder"):
        out.append("")
        out.append("lockorder/observed acquisition edges "
                   "(runtime sanitizer):")
        for e in agg["lockorder"]:
            out.append("  %s -> %s" % (e["src"], e["dst"]))
    out.extend(_render_numerics(agg.get("numerics") or {}, fmt))
    out.extend(_render_compress(agg.get("compress") or [],
                                agg.get("gauges") or {}, fmt))
    out.extend(_render_elastic(agg.get("elastic") or [], fmt))
    out.extend(_render_serve(agg.get("serve") or {},
                             agg.get("counters") or {}, fmt))
    out.extend(_render_histograms(agg.get("histograms") or {}, fmt))
    out.extend(_render_traces(agg.get("traces") or {}))
    out.extend(_render_incidents(agg.get("incidents") or [], fmt))
    out.extend(_render_chaos_audit(agg.get("chaos_audit"), fmt))
    out.extend(_render_hbm(agg.get("hbm") or {}, fmt))
    return "\n".join(out)


def _render_chaos_audit(rec, fmt="markdown"):
    """Fault-injection coverage matrix from the lint/chaos_audit
    telemetry event: fault point | injection | covering test."""
    if not rec:
        return []
    out = ["", "chaos coverage (%s): %d mode(s), %d fault point(s), "
           "%d problem(s)"
           % ("OK" if rec.get("ok") else "FAILING",
              rec.get("modes", 0), rec.get("points", 0),
              rec.get("problems", 0))]
    matrix = rec.get("matrix") or []
    if not matrix:
        return out
    header = ["fault point", "site", "injection", "covering test"]
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")
    for row in matrix:
        kind, site, modes, tests = (list(row) + ["", "", "", ""])[:4]
        vals = [str(kind), str(site), str(modes) or "-",
                str(tests) or "-"]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    return out


def _render_histograms(histograms, fmt="markdown"):
    """Quantile digest table from the snapshot records' mergeable
    histogram dicts — one row per metric (serve latency, queue wait,
    step time, prefetch stages), quantiles computed bucket-side."""
    if not histograms:
        return []
    header = ["histogram", "count", "mean-ms", "p50-ms", "p90-ms",
              "p99-ms", "max-ms"]
    out = ["", "histograms (log-bucketed, merged across snapshots):"]
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")

    def g(v):
        return "%.6g" % v if v is not None else "-"

    for name in sorted(histograms):
        h = histograms[name]
        n = h.get("count", 0)
        vals = [name, str(n),
                g(h.get("sum", 0.0) / n if n else None),
                g(_hist_quantile(h, 0.50)), g(_hist_quantile(h, 0.90)),
                g(_hist_quantile(h, 0.99)), g(h.get("max"))]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    return out


def _render_traces(traces):
    """One summary line: how many distinct traces the journal carries
    (render any single one with ``--trace <id>``)."""
    if not traces:
        return []
    ids = sorted(traces, key=lambda t: traces[t][0].get("ts") or 0)
    shown = ", ".join(ids[:4]) + (", ..." if len(ids) > 4 else "")
    return ["", "traces: %d distinct (%s) — render one with "
            "--trace <id>" % (len(ids), shown)]


def _render_incidents(incidents, fmt="markdown"):
    """Flight-recorder dump journal: one row per committed, capped or
    failed bundle."""
    if not incidents:
        return []
    header = ["incident", "reason", "path/error"]
    out = ["", "flight-recorder incidents:"]
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")
    for e in incidents:
        vals = [str(e.get("event", "?")), str(e.get("reason", "?")),
                str(e.get("path") or e.get("error") or "-")]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    return out


def render_trace(agg, trace_id, fmt="markdown"):
    """Waterfall for ONE trace: its records ordered by timestamp,
    span names indented by the ``sid``/``parent`` nesting, offsets
    relative to the trace's first record — readable straight off a
    per-rank export or a collector-merged multi-rank file."""
    recs = (agg.get("traces") or {}).get(str(trace_id))
    if not recs:
        return "trace %s: not found (%d traces in input)" \
            % (trace_id, len(agg.get("traces") or {}))
    recs = sorted(recs, key=lambda r: r.get("ts") or 0)
    t0 = recs[0].get("ts") or 0
    depth = {}
    for r in recs:
        sid = r.get("sid")
        if sid is not None:
            depth[sid] = depth.get(r.get("parent"), 0) + 1
    header = ["offset-ms", "dur-ms", "rank", "kind", "name", "detail"]
    out = ["trace %s (%d records):" % (trace_id, len(recs))]
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")
    skip = ("ts", "kind", "name", "trace", "sid", "parent", "tid",
            "dur_ms", "rank")
    for r in recs:
        ind = "  " * (depth.get(r.get("sid"),
                                depth.get(r.get("parent"), 0)))
        detail = " ".join(
            "%s=%s" % (k, r[k]) for k in sorted(r)
            if k not in skip and r[k] is not None)
        vals = ["%.3f" % (((r.get("ts") or 0) - t0) * 1e3),
                "%.3f" % r["dur_ms"] if r.get("dur_ms") is not None
                else "-",
                "-" if r.get("rank") is None else str(r["rank"]),
                str(r.get("kind", "?")),
                ind + str(r.get("name", "?")), detail or "-"]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    return "\n".join(out)


def parse_incident(path):
    """Load a flight-recorder bundle directory
    (``incident-<ts>-<reason>/``) into one dict: config + snapshot +
    histogram dicts + the parsed journal tail."""
    def load(name, default):
        p = os.path.join(path, name)
        if not os.path.exists(p):
            return default
        try:
            with open(p) as f:
                return json.load(f)
        except ValueError:
            return default

    journal_path = os.path.join(path, "journal.jsonl")
    journal_agg = None
    n_journal = 0
    if os.path.exists(journal_path):
        with open(journal_path) as f:
            lines = f.readlines()
        n_journal = len(lines)
        journal_agg = parse_jsonl(lines)
    return {"path": path, "config": load("config.json", {}),
            "snapshot": load("snapshot.json", {}),
            "histograms": load("histograms.json", {}),
            "lockgraph": load("lockgraph.json", []),
            "hbm": load("hbm.json", []),
            "journal": journal_agg, "journal_records": n_journal}


def render_incident(inc, fmt="markdown"):
    """Bundle summary: the trigger line (reason/detail/rank/pid), the
    journal-tail census (event kinds, serve outcomes, traces),
    histogram quantiles and final counters."""
    cfg = inc.get("config") or {}
    out = ["incident bundle %s" % inc.get("path"),
           "  reason: %s" % cfg.get("reason"),
           "  detail: %s" % cfg.get("detail"),
           "  rank=%s pid=%s ts=%s" % (cfg.get("rank"), cfg.get("pid"),
                                       cfg.get("ts"))]
    if cfg.get("extra"):
        out.append("  extra: %s" % json.dumps(cfg["extra"],
                                              default=str,
                                              sort_keys=True))
    snap = inc.get("snapshot") or {}
    counters = snap.get("counters") or {}
    if counters:
        out.append("  counters: %s"
                   % " ".join("%s=%s" % (k, counters[k])
                              for k in sorted(counters)))
    out.extend(_render_histograms(inc.get("histograms") or {}, fmt))
    j = inc.get("journal")
    if j is not None:
        out.append("")
        out.append("journal tail (%d records):"
                   % inc.get("journal_records", 0))
        out.append("  traces: %d distinct"
                   % len(j.get("traces") or {}))
        out.extend(_render_serve(j.get("serve") or {},
                                 j.get("counters") or {}, fmt))
        out.extend(_render_elastic(j.get("elastic") or [], fmt))
        out.extend(_render_incidents(j.get("incidents") or [], fmt))
    if inc.get("lockgraph"):
        out.append("")
        out.append("lock-order edges at dump: %d" % len(inc["lockgraph"]))
    return "\n".join(out)


def _render_serve(serve, counters, fmt="markdown"):
    """Serving journal census: dispatched-batch aggregates (count, mean
    fill %, mean queue depth, mean batch wait) plus one row per event
    kind (sheds, timeouts, rejects, watchdog fires, quarantines, state
    transitions) — the client-visible failure envelope at a glance."""
    events = (serve or {}).get("events") or {}
    if not events and not any(k.startswith("serve.") for k in counters):
        return []
    out = ["", "serve journal census:"]
    n = serve.get("batches", 0)
    if n:
        out.append(
            "  batches=%d mean-fill=%.1f%% mean-queue-depth=%.2f "
            "mean-wait-ms=%.3f"
            % (n, serve["fill_pct_sum"] / n,
               serve["queue_depth_sum"] / n, serve["wait_ms_sum"] / n))
    header = ["event", "count"]
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")
    for name in sorted(events):
        vals = ["serve/%s" % name, str(events[name])]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    counts = " ".join("%s=%s" % (k.split(".", 1)[1], counters[k])
                      for k in sorted(counters)
                      if k.startswith("serve."))
    if counts:
        out.append("  counters: %s" % counts)
    if serve.get("states"):
        out.append("  state transitions: %s"
                   % " ".join(serve["states"]))
    return out


def _render_elastic(elastic, fmt="markdown"):
    """Elastic/checkpoint journal census: one row per recovery-protocol
    transition (elastic/detect, elastic/reshard, ckpt/write,
    ckpt/restore, ...) with the step, world-size transition, bytes
    moved and duration."""
    if not elastic:
        return []
    header = ["event", "step", "world", "bytes", "ms", "detail"]
    out = ["", "elastic/checkpoint journal census:"]
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")

    def cell(v):
        return "-" if v is None else str(v)

    for e in elastic:
        vals = [e["event"], cell(e.get("step")), cell(e.get("world")),
                cell(e.get("bytes")), cell(e.get("dur_ms")),
                cell(e.get("detail"))]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    return out


def _render_compress(compress, gauges, fmt="markdown"):
    """Gradient-compression census from the compress/decision journal:
    one row per knob resolution (mode, forced or disabled, dp extent, and the
    schedule-arithmetic bytes on the wire vs the f32 baseline), headed
    by the final wire-savings gauges."""
    if not compress and not any(k.startswith("compression.")
                                for k in gauges):
        return []
    out = ["", "gradient compression census:"]
    saved = gauges.get("compression.bytes_saved")
    scale = gauges.get("compression.scale_bytes")
    if saved is not None or scale is not None:
        out.append("  wire bytes saved/step: %s (scale side tensor: %s)"
                   % ("%.6g" % saved if saved is not None else "-",
                      "%.6g" % scale if scale is not None else "-"))
    if not compress:
        return out
    header = ["mode", "requested", "path", "dp", "params", "dtype",
              "wire-B", "scale-B", "f32-B", "ratio"]
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")

    def cell(v):
        return "-" if v is None else str(v)

    for d in compress:
        vals = [cell(d.get("mode")), cell(d.get("requested")),
                cell(d.get("path")), cell(d.get("dp")),
                cell(d.get("params")), cell(d.get("dtype")), cell(d.get("wire_bytes")),
                cell(d.get("scale_bytes")), cell(d.get("f32_bytes")),
                cell(d.get("ratio"))]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    return out


def _render_numerics(numerics, fmt="markdown"):
    """Per-leaf observed-dtype + finite-gauge table from the
    numerics/observed journal events (runtime numerics sanitizer /
    Monitor nan_guard)."""
    if not numerics:
        return []
    header = ["leaf", "observed-dtypes", "nonfinite", "size",
              "first-bad-step"]
    out = ["", "numerics/observed leaves (runtime sanitizer):"]
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")
    for leaf in sorted(numerics):
        n = numerics[leaf]
        vals = [leaf, " -> ".join(n["dtypes"]) or "-",
                str(n["nonfinite"]),
                "-" if n.get("size") is None else str(n["size"]),
                "-" if n.get("first_bad_step") is None
                else str(n["first_bad_step"])]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    return out


# rule-id prefix -> checker family (docs/LINTING.md catalog sections;
# mirrors tools.lint.rule_family — this script stays import-free)
_RULE_FAMILIES = {"trace": "trace-safety", "retrace": "retrace",
                  "donate": "donation", "pallas": "pallas",
                  "shard": "sharding", "conc": "concurrency",
                  "num": "numerics", "err": "errorflow",
                  "res": "errorflow", "lint": "meta"}


def _rule_family(rule):
    return _RULE_FAMILIES.get(rule.split("-", 1)[0], "other")


def parse_lint(text):
    """Parse a graftlint ``--format json`` report into
    ``{"counts": {...}, "by_rule": {rule: n}, "by_file": {path: n},
    "findings": [...], "hbm": {...}}`` (new findings only;
    baselined/suppressed are reflected in counts).

    Also accepts a telemetry JSONL sink instead of a report: the
    ``lint/gate`` event supplies the counts and the ``hbm/estimate``
    events the bytes-per-chip table (one file carries both when the
    tier-1 gate and a training run share a journal)."""
    data = None
    try:
        data = json.loads(text)
    except ValueError:
        pass
    if not isinstance(data, dict):
        agg = parse_jsonl(text.splitlines())
        gate = agg.get("lint_gate") or {}
        counts = {k: gate.get(k, 0)
                  for k in ("new", "baselined", "suppressed")}
        counts["total"] = sum(counts.values())
        return {"counts": counts, "by_rule": {}, "by_file": {},
                "findings": [], "hbm": agg.get("hbm") or {}}
    by_rule = {}
    by_file = {}
    for f in data.get("findings", []):
        by_rule[f["rule"]] = by_rule.get(f["rule"], 0) + 1
        by_file[f["path"]] = by_file.get(f["path"], 0) + 1
    return {"counts": data.get("counts", {}), "by_rule": by_rule,
            "by_file": by_file, "findings": data.get("findings", []),
            "hbm": data.get("hbm_estimates", {})}


def render_lint(agg, fmt="markdown"):
    """Summary table (new/baselined/suppressed + per-family/rule
    counts), one line per new finding, and the static-HBM table when
    the input journal carried hbm/estimate events."""
    c = agg["counts"]
    header = ["family", "rule", "new"]
    out = []
    if fmt == "markdown":
        out.append("lint: %d new, %d baselined, %d suppressed (%d total)"
                   % (c.get("new", 0), c.get("baselined", 0),
                      c.get("suppressed", 0), c.get("total", 0)))
        out.append("")
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")
    else:
        out.append("new\t%d" % c.get("new", 0))
        out.append("baselined\t%d" % c.get("baselined", 0))
        out.append("suppressed\t%d" % c.get("suppressed", 0))
    for rule in sorted(agg["by_rule"],
                       key=lambda r: (_rule_family(r), r)):
        vals = [_rule_family(rule), rule, str(agg["by_rule"][rule])]
        out.append("| " + " | ".join(vals) + " |" if fmt == "markdown"
                   else "\t".join(vals))
    if agg["findings"]:
        out.append("")
        for f in agg["findings"]:
            out.append("%s:%d: %s [%s] (in %s)"
                       % (f["path"], f["line"], f["message"], f["rule"],
                          f.get("context", "?")))
    out.extend(_render_hbm(agg.get("hbm") or {}, fmt))
    return "\n".join(out)


def render(rows, fmt="markdown"):
    train_metrics = sorted({k for r in rows.values() for k in r["train"]})
    val_metrics = sorted({k for r in rows.values() for k in r["val"]})
    has_step = any(r["step_ms"] for r in rows.values())
    has_ring = any(r["ring"] for r in rows.values())
    header = (["epoch"] + ["train-%s" % m for m in train_metrics]
              + ["val-%s" % m for m in val_metrics] + ["time", "speed"]
              + (["step-ms"] if has_step else [])
              + (["ring"] if has_ring else []))
    out = []
    if fmt == "markdown":
        out.append("| " + " | ".join(header) + " |")
        out.append("| " + " | ".join("---" for _ in header) + " |")

    def mean(xs):
        return (sum(xs) / len(xs)) if xs else None
    for e in sorted(rows):
        r = rows[e]
        cells = ([r["train"].get(m) for m in train_metrics]
                 + [r["val"].get(m) for m in val_metrics]
                 + [r["time"], mean(r["speed"])]
                 + ([mean(r["step_ms"])] if has_step else [])
                 + ([mean(r["ring"])] if has_ring else []))
        vals = [str(e)] + ["%.6g" % v if v is not None else "-"
                           for v in cells]
        if fmt == "markdown":
            out.append("| " + " | ".join(vals) + " |")
        else:
            out.append("\t".join(vals))
    return "\n".join(out)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("logfile", nargs="?", default="-")
    parser.add_argument("--format", choices=["markdown", "tsv"],
                        default="markdown")
    parser.add_argument("--jsonl", action="store_true",
                        help="input is a telemetry JSONL metrics sink, "
                             "not a text training log")
    parser.add_argument("--lint", action="store_true",
                        help="input is a graftlint --format json report "
                             "(python -m tools.lint --format json)")
    parser.add_argument("--trace", metavar="ID",
                        help="with --jsonl: render ONE trace as a "
                             "waterfall table instead of the summary")
    parser.add_argument("--incident", metavar="DIR",
                        help="summarise a flight-recorder bundle "
                             "directory (incident-<ts>-<reason>/); "
                             "no logfile needed")
    args = parser.parse_args()
    if args.incident:
        print(render_incident(parse_incident(args.incident),
                              args.format))
        return
    lines = sys.stdin if args.logfile == "-" else open(args.logfile)
    if args.lint:
        print(render_lint(parse_lint(lines.read()), args.format))
    elif args.trace:
        print(render_trace(parse_jsonl(lines), args.trace, args.format))
    elif args.jsonl:
        print(render_jsonl(parse_jsonl(lines), args.format))
    else:
        print(render(parse(lines), args.format))


if __name__ == "__main__":
    main()
