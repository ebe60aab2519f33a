#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process, no subprocess.  Reads the cell from ``BENCHMARK.json``, finds
its configuration (``benchmark/configs/<config>/``), its traffic mix
(``benchmark/traffic/<mix>.json``), the mix's driver
(``benchmark/drivers/<driver>.py``) and, for a traced run, one reader per
per-layer metric (``benchmark/layer_metrics/<metric>.py``) BY NAME: a new
cell, configuration, mix, driver or metric is new files and new entries,
never an edit here.  Prints progress lines, then as the LAST line of stdout
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``.

A run that finds no TPU, fewer chips than the cell asks for, or a device
kind that ``peaks.json`` does not know exits non-zero and prints no result.
``--rehearse`` is the only way to run on the CPU: toy sizes from the
configuration's ``rehearsal`` group, for tests of the control flow; it
prints counts only, every time, rate and share withheld (``null``).  No
chip run uses it.
"""
import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _merge(base, over):
    out = dict(base)
    for key, value in over.items():
        out[key] = _merge(out[key], value) \
            if isinstance(value, dict) and isinstance(out.get(key), dict) \
            else value
    return out


def _find(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit("run.py: no %s named %r" % (what, name))


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def _compile_cache():
    """JAX's persistent compilation cache: where the machine names a
    directory (``JAX_COMPILATION_CACHE_DIR``) that one, and no other is set
    in code; otherwise the fixed ``<checkout>/.jax_cache`` (the path is
    part of the cache's key).  Every program is kept, however quick its
    compile: the hundred small programs of model set-up hit too."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spec", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="the file of cells (tests pass their own; "
                         "traffic files beside it, in traffic/, are found "
                         "before benchmark/traffic/)")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU, counts only; never used "
                         "on the chip")
    args = ap.parse_args(argv)

    with open(args.spec) as f:
        spec = json.load(f)
    cell = _find(spec["workloads"], args.workload, "workload")
    config_entry = _find(spec["configs"], cell["config"], "configuration")
    config_path = os.path.join(ROOT, config_entry["file"])
    with open(config_path) as f:
        config = json.load(f)
    traffic_path = next(
        (p for p in (os.path.join(os.path.dirname(os.path.abspath(
            args.spec)), "traffic", cell["traffic"] + ".json"),
            os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
         if os.path.isfile(p)), None)
    if traffic_path is None:
        raise SystemExit("run.py: no traffic file for %r" % cell["traffic"])
    with open(traffic_path) as f:
        traffic = json.load(f)
    sizes = {k: v for k, v in config.items() if k != "rehearsal"}
    chips = int(cell["chips"])
    if args.rehearse:
        sizes = _merge(sizes, config["rehearsal"])
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1 and "xla_force_host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=%d" % chips)

    sys.path.insert(0, ROOT)
    import jax
    from benchmark import harness

    cache_dir = _compile_cache()
    devices = jax.devices()
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    kind = devices[0].device_kind
    if not args.rehearse:
        if devices[0].platform != "tpu":
            raise SystemExit("run.py: jax.devices()[0] is %r, not a TPU"
                             % (devices[0],))
        if kind not in peaks:
            raise SystemExit("run.py: no published peaks for device kind "
                             "%r in peaks.json" % kind)
    if len(devices) < chips:
        raise SystemExit("run.py: the cell needs %d chips, jax sees %d"
                         % (chips, len(devices)))
    devices = devices[:chips]

    # a seed a little over 2**31 has to fit numpy's and jax's 32 bits
    seed = args.seed % (2 ** 31 - 1)
    model = harness.load_module(
        os.path.join(os.path.dirname(config_path), "model.py"),
        "bench_model")
    driver = harness.load_module(
        os.path.join(HERE, "drivers", traffic["driver"] + ".py"),
        "bench_driver")
    bench = harness.Run(T0, sizes, traffic, model, seed, args.seconds,
                        bool(args.trace), devices)
    bench.say("device", platform=devices[0].platform, kind=kind,
              chips=chips, compile_cache=cache_dir, rehearsal=args.rehearse,
              seed=seed)
    result = driver.run(bench)

    # ---- the metrics of this run: end to end untraced, per layer traced
    declared = spec["per_layer"] if args.trace else spec["end_to_end"]
    values = {}
    if args.trace:
        facts = dict(result["facts"], spans=bench.spans, trace=bench.trace,
                     compiles_in_window=bench.compiles_in_window(),
                     chips=chips, sizes=sizes, model=model,
                     peaks=peaks.get(kind), setup_s=bench.setup_s)
        for metric in declared:
            if _reports(metric, cell["name"]):
                reader = harness.load_module(os.path.join(
                    HERE, "layer_metrics", metric["name"] + ".py"),
                    "bench_metric")
                value = reader.read(facts)
                if value is not None:
                    values[metric["name"]] = value
        if not args.rehearse:
            bench.say("end_to_end_of_traced_run", setup_s=bench.setup_s,
                      **result["end_to_end"])
    else:
        measured = dict(result["end_to_end"], setup_s=bench.setup_s)
        for metric in declared:
            if _reports(metric, cell["name"]):
                values[metric["name"]] = measured[metric["name"]]
    units = {m["name"]: m["unit"] for m in declared}
    if args.rehearse:
        # a CPU number is never printed under a device metric's name
        values = {name: value if units[name] == "count" else None
                  for name, value in values.items()}
    device = {"platform": devices[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": None if args.rehearse
              else result["memory_peak_bytes"]}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"],
            "metrics": {name: {"value": value, "unit": units[name]}
                        for name, value in values.items()},
            "device": device}
    if args.trace and bench.trace is not None and not args.rehearse:
        device["busy_s"] = bench.trace["busy_s"]
        device["window_s"] = bench.trace["window_s"]
        line["breakdown"] = {"device_ops": bench.trace["device_ops"],
                             "idle_gaps": bench.trace["idle_gaps"]}
    if args.rehearse:
        device["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
