#!/usr/bin/env python
"""Communication-bandwidth probe (reference ``tools/bandwidth/measure.py``).

The reference measures kvstore push/pull GB/s across GPUs to size
gradient aggregation; the TPU-native equivalents are the three links a
training step actually exercises:

  * ``h2d`` / ``d2h`` — host↔device ``device_put`` / ``np.asarray``
    transfer (the input-pipeline link),
  * ``copy`` — on-device HBM copy bandwidth (a donated a+0 roundtrip),
  * ``allreduce`` — jitted ``psum`` over all visible devices (the
    gradient-aggregation link; ICI on real multi-chip, shared memory on
    the virtual CPU mesh).

Sizes sweep powers of two like the reference's ``--num-batches`` sweep.

    python tools/bandwidth.py
    python tools/bandwidth.py --sizes-mb 1,16,64 --format tsv
"""
import argparse
import json
import time

import numpy as onp


def _sync(y):
    """Wait for the device to finish ``y`` (host arrays are already done)."""
    if y is not None and not isinstance(y, onp.ndarray):
        y.block_until_ready()


def _bench(fn, sync, warmup=2, iters=5):
    for _ in range(warmup):
        sync(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    sync(out)
    return (time.perf_counter() - t0) / iters


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default="1,4,16,64")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--format", default="json", choices=["json", "tsv"])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    dev = devs[0]
    rows = []
    # hoisted out of the size loop (graftlint retrace-jit-in-loop): one
    # callable keeps its per-shape compile cache across iterations
    add0 = jax.jit(lambda a: a + 0.0)
    for mb in [float(s) for s in args.sizes_mb.split(",")]:
        n = int(mb * 1e6 / 4)
        host = onp.random.RandomState(0).rand(n).astype("float32")
        row = {"size_mb": mb, "devices": len(devs)}

        x = jax.device_put(host, dev)
        _sync(x)
        row["h2d_gbs"] = round(mb / 1e3 / _bench(
            lambda: jax.device_put(host, dev), _sync, iters=args.iters), 2)
        row["d2h_gbs"] = round(mb / 1e3 / _bench(
            lambda: onp.asarray(x), lambda y: None, iters=args.iters), 2)

        # read + write: 2x the buffer moves through HBM per call
        row["copy_gbs"] = round(2 * mb / 1e3 / _bench(
            lambda: add0(x), _sync, iters=args.iters), 2)

        if len(devs) > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            mesh = Mesh(onp.asarray(devs), ("dp",))
            sharded = jax.device_put(
                onp.tile(host[None], (len(devs), 1)),
                NamedSharding(mesh, P("dp", None)))

            @jax.jit
            def ar(v):
                return jax.shard_map(
                    lambda s: jax.lax.psum(s, "dp"), mesh=mesh,
                    in_specs=P("dp", None), out_specs=P(None, None),
                    check_vma=False)(v)
            # algorithmic bytes: each device contributes its shard once
            row["allreduce_gbs"] = round(
                mb * len(devs) / 1e3 / _bench(
                    lambda: ar(sharded), _sync, iters=args.iters), 2)
        rows.append(row)

    if args.format == "tsv":
        keys = list(rows[0])
        print("\t".join(keys))
        for r in rows:
            print("\t".join(str(r.get(k, "")) for k in keys))
    else:
        for r in rows:
            print(json.dumps(r))
    return rows


if __name__ == "__main__":
    main()
