#!/usr/bin/env python3
"""Cut a small recording out of a traced chip run's ``.xplane.pb`` for
``test_reduce_trace.py``: the device planes' ``XLA Ops`` and the
benchmark's host spans inside ``--ms`` milliseconds from the start of the
traced window, nothing else.  A tool for whoever renews the recording, not
part of any run:

    python3 benchmark/tests/cut_xplane.py full.xplane.pb out.xplane.pb --ms 6

Needs the ``xplane_pb2`` that ships inside the installed tensorflow wheel
(loaded by path, tensorflow itself is not imported).
"""
import argparse
import glob
import importlib.util
import os
import sys
import sysconfig


def _xplane_pb2():
    hits = glob.glob(os.path.join(
        sysconfig.get_paths()["purelib"], "tensorflow", "tsl", "profiler",
        "protobuf", "xplane_pb2.py"))
    if not hits:
        raise SystemExit("no xplane_pb2.py in the installed tensorflow")
    spec = importlib.util.spec_from_file_location("xplane_pb2", hits[0])
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("source")
    ap.add_argument("target")
    ap.add_argument("--ms", type=float, default=6.0)
    args = ap.parse_args()
    pb = _xplane_pb2()
    space = pb.XSpace()
    with open(args.source, "rb") as f:
        space.ParseFromString(f.read())

    def spans_of(plane):
        names = {k: m.name for k, m in plane.event_metadata.items()}
        for line in plane.lines:
            for e in line.events:
                yield line, e, names.get(e.metadata_id, "")

    # the traced window's start, in absolute picoseconds
    start_ps = None
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            for line, e, name in spans_of(plane):
                if name == "bench.traced_window":
                    start_ps = line.timestamp_ns * 1000 + e.offset_ps
    if start_ps is None:
        raise SystemExit("no bench.traced_window span in the trace")
    end_ps = start_ps + int(args.ms * 1e9)

    out = pb.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:"):
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        kept = out.planes.add()
        kept.id, kept.name = plane.id, plane.name
        used = set()
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            picked = []
            for e in line.events:
                at = line.timestamp_ns * 1000 + e.offset_ps
                name = names.get(e.metadata_id, "")
                if device and at + e.duration_ps > start_ps and at < end_ps:
                    picked.append(e)
                elif not device and name.startswith("bench.") \
                        and at < end_ps and at + e.duration_ps > start_ps:
                    picked.append(e)
            if not picked:
                continue
            new = kept.lines.add()
            new.id, new.name = line.id, line.name
            new.timestamp_ns = line.timestamp_ns
            for e in picked:
                copy = new.events.add()
                copy.metadata_id = e.metadata_id
                copy.offset_ps = e.offset_ps
                # a span that runs on past the cut ends with it
                copy.duration_ps = min(
                    e.duration_ps,
                    end_ps - line.timestamp_ns * 1000 - e.offset_ps)
                used.add(e.metadata_id)
        for key in used:
            kept.event_metadata[key].id = key
            kept.event_metadata[key].name = names[key]
    with open(args.target, "wb") as f:
        f.write(out.SerializeToString())
    print("%s: %d bytes" % (args.target, os.path.getsize(args.target)))


if __name__ == "__main__":
    sys.exit(main())
