"""Driver ``serve_closed``: a closed loop of client threads against one
``serve.InferenceServer`` over the configuration's forward pass.

Each of ``clients`` threads submits one request, waits for its terminal
outcome, and submits its next: callers that each wait for a reply, as a
front end with a bounded worker pool makes them.  Images come from a seeded
pool, each client walking it in its own seeded order.  ``serve_config`` is
handed to ``ServeConfig`` as keywords (empty: the server as shipped).

A request that does not end in ``result`` inside its deadline is attempted
and failed, and counts at its deadline in the latencies.  EVERY result of
the window is compared with the plain reference's logits for its image,
which were computed at set-up for the whole pool.
"""
import gc
import statistics
import threading
import time

import numpy as onp


def run(bench):
    from mxnet_tpu import serve, telemetry
    from benchmark import correct, harness

    traffic = bench.traffic
    clients, pool = int(traffic["clients"]), int(traffic["pool"])
    deadline_ms = float(traffic["deadline_ms"])

    # ---- set-up: weights, pool and its reference from the seed; the
    # server compiles its buckets in start(); a round of requests through
    # every client warms the host path and is checked like the window's
    t = time.perf_counter()
    built = bench.model.build_serve(bench.sizes, bench.seed, pool)
    images, reference = built["images"], built["reference"]
    build_s = time.perf_counter() - t
    t = time.perf_counter()
    config = serve.ServeConfig(default_deadline_ms=deadline_ms,
                               **traffic.get("serve_config", {}))
    srv = serve.InferenceServer(built["net"],
                                feature_shape=built["feature_shape"],
                                dtype=built["dtype"], config=config,
                                name="bench")
    srv.start()
    start_s = time.perf_counter() - t
    orders = [onp.random.RandomState([bench.seed, c]).permutation(pool)
              for c in range(clients)]
    stop_at = [None]
    records = [[] for _ in range(clients)]   # (image, kind, latency_ms, out)
    client_cpu_s = [0.0] * clients           # the generator's own CPU time

    def client(c):
        mine, order, at = records[c], orders[c], 0
        cpu0 = time.thread_time()
        while time.perf_counter() < stop_at[0]:
            image = int(order[at % pool])
            at += 1
            with bench.span("bench.client_request"):
                handle = srv.submit(images[image])
                out = handle.outcome(timeout=deadline_ms / 1e3 + 5.0)
            kind = out[0] if out is not None else "lost"
            latency = handle.latency_ms()
            mine.append((image, kind,
                         latency if kind == "result" else deadline_ms,
                         out[1] if kind == "result" else None))
        client_cpu_s[c] = time.thread_time() - cpu0

    def drive(seconds, trace_after=None):
        """Run every client for ``seconds``; returns the time until the
        last of them had its last outcome.  The main thread, idle
        meanwhile, starts the profiler ``trace_after`` seconds in."""
        for mine in records:
            del mine[:]
        stop_at[0] = time.perf_counter() + seconds
        threads = [threading.Thread(target=client, args=(c,),
                                    name="bench-client-%d" % c)
                   for c in range(clients)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        if trace_after is not None:
            time.sleep(trace_after)
            bench.trace_start()
        for th in threads:
            th.join()
        return time.perf_counter() - t0

    def verdict():
        """(attempted, ok, latencies, worst check) of what was recorded."""
        done = [r for mine in records for r in mine]
        ok = [r for r in done if r[1] == "result" and r[2] <= deadline_ms]
        worst = {"ok": True, "max_err": 0.0}
        for image, _, _, out in ok:
            check = correct.logits_agree(out, reference[image])
            if not check["ok"] or check["max_err"] > worst["max_err"]:
                worst = check
            if not check["ok"]:
                break
        return len(done), ok, [r[2] for r in done], worst

    try:
        t = time.perf_counter()
        drive(1.0)
        attempted, ok, _, warm_check = verdict()
        bench.say("setup", build_s=build_s, server_start_s=start_s,
                  warm_round_s=time.perf_counter() - t,
                  warm_requests=attempted, warm_ok=len(ok),
                  reference_check=warm_check,
                  compiles=bench.compiles.snapshot(),
                  buckets=srv.stats()["buckets"])
        gc.collect()

        # ---- the window
        counters0 = {name: telemetry.counter(name) for name in
                     ("serve.dispatches", "serve.results", "serve.requests")}
        hists0 = {name: (telemetry.histogram(name).to_dict()
                         if telemetry.histogram(name) else {})
                  for name in ("serve.queue_wait", "serve.dispatch")}
        bench.window_opens()
        cpu_at_open = time.process_time()
        window_s = drive(
            bench.seconds,
            trace_after=bench.seconds - min(bench.seconds / 2,
                                            bench.trace_seconds)
            if bench.traced else None)
        process_cpu_s = time.process_time() - cpu_at_open
        if bench.traced:
            bench.trace_stop()
        counters = {name: telemetry.counter(name) - counters0[name]
                    for name in counters0}
        hists = {}
        for name, before in hists0.items():
            live = telemetry.histogram(name)
            if live is not None:
                delta = live.since(before)
                hists[name] = {"count": delta.count, "sum_ms": delta.sum}
        recompiles = srv.steady_state_recompiles()
        stats = srv.stats()
    finally:
        srv.close()

    attempted, ok, latencies, check = verdict()
    bench.say("window", attempted=attempted, ok=len(ok), window_s=window_s,
              reference_check=check, counters=counters, hists=hists,
              steady_state_recompiles=recompiles, server=stats,
              generator_cpu_s=sum(client_cpu_s),
              process_cpu_s=process_cpu_s,
              latency_ms_median=statistics.median(latencies),
              memory_stats=bench.devices[0].memory_stats())
    return {
        "correct": bool(warm_check["ok"] and check["ok"] and ok),
        "attempted": attempted, "failed": attempted - len(ok),
        "end_to_end": {"serve_ok_per_s": len(ok) / window_s,
                       "serve_latency_p95_ms": harness.p95(latencies)},
        "facts": {"window_s": window_s, "counters": counters,
                  "hists": hists,
                  "steady_state_recompiles": sum(recompiles.values())},
        "memory_peak_bytes": bench.memory_peak_bytes()}
