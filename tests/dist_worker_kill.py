"""Worker body for the chaos / failure-detection matrix (reference
``include/mxnet/kvstore.h:353`` get_num_dead_node over ps-lite
heartbeats; here the jax coordination service's liveness view plus the
elastic runtime on top of it).

Modes, selected by ``MXTPU_KILL_MODE``:

* (default) ``liveness`` — 3 processes: rank 2 dies (os._exit, no
  cleanup — a crash, not a clean shutdown) right after joining; ranks
  0 and 1 must observe ``kv.num_dead_node()`` transition 0 -> 1.
* ``elastic`` — 3 processes TRAINING: the chaos ``kill_worker`` fault
  (armed via MXNET_TPU_CHAOS) preempts rank 2 mid-epoch; the
  survivors' ``ElasticContext`` detects the departure through the KV
  heartbeat liveness view, re-forms the mesh over their surviving
  devices, and training resumes mid-epoch with the loss still
  decreasing.  (Cross-process collectives are version-gated on this
  backend — each worker trains its replica on its local mesh; the
  cross-extent ZeRO re-shard math is covered in-process by
  tests/test_elastic.py.)
* ``ckpt_phase1`` — N processes train with an async CheckpointManager
  writing into MXTPU_CKPT_DIR, then die abruptly (os._exit, no
  shutdown barrier — a coordinator loss).
* ``ckpt_phase2`` — launched as a NEW, smaller job: restores from the
  manifest the dead job left behind, verifies the state bitwise
  against a deterministic recomputation, and keeps training.
"""
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MXNET_TPU_RECOVERABLE"] = "1"      # survivors keep running
os.environ.setdefault("MXNET_TPU_HEARTBEAT_TIMEOUT", "10")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _exit_ordered(kv, rank, expect_done):
    """os._exit with leader-last ordering: rank 0's process hosts the
    coordination service, and on this jax its death fatally terminates
    (SIGABRT) any peer still running, regardless of recoverability —
    so non-leader ranks drop a done-key and exit first, and rank 0
    waits for ``expect_done`` of them (dead ranks never write one)
    before pulling the coordinator down."""
    from jax._src import distributed as _dist
    client = getattr(_dist.global_state, "client", None)
    if client is None or kv.num_workers <= 1:
        os._exit(0)
    if rank != 0:
        client.key_value_set("mxtpu/done/%d" % rank, "1")
        os._exit(0)
    deadline = time.time() + 30
    got = set()
    while len(got) < expect_done and time.time() < deadline:
        for r in range(1, kv.num_workers):
            if r in got:
                continue
            try:
                client.blocking_key_value_get("mxtpu/done/%d" % r, 100)
                got.add(r)
            except Exception:
                pass
    time.sleep(0.5)     # let the peers' os._exit land
    os._exit(0)


def _build_step(shard=True):
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    onp.random.seed(42)
    mx.random.seed(42)
    net = nn.HybridSequential()
    net.add(nn.Dense(7, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    X = onp.random.RandomState(0).randn(16, 9).astype("float32")
    Y = onp.random.RandomState(1).randint(0, 4, 16).astype("float32")
    net(mx.nd.array(X))
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    # this worker's LOCAL devices only: cross-process computations are
    # version-gated on the CPU backend (see _cpu_multiprocess in
    # test_dist_multiprocess.py) — the elastic protocol under test is
    # process-level detection + re-formation, not DCN collectives
    mesh = parallel.device_mesh(devices=jax.local_devices())
    step = parallel.DataParallelStep(
        net, lambda o, l: L(o, l),
        mx.optimizer.SGD(learning_rate=0.1, momentum=0.9), mesh=mesh,
        shard_optimizer=shard)
    batch = (mx.nd.array(X), mx.nd.array(Y))
    return step, batch


def main_liveness():
    from mxnet_tpu import kvstore

    kv = kvstore.create("dist_sync")
    if kv.rank == 2:
        # crash without any coordination-service cleanup
        sys.stdout.flush()
        os._exit(0)

    # freshly joined: everyone alive (allow the service a beat to settle)
    assert kv.num_dead_node(timeout=5) in (0, 1)

    deadline = time.time() + 90
    seen_dead = 0
    while time.time() < deadline:
        seen_dead = kv.num_dead_node(timeout=5)
        if seen_dead >= 1:
            break
        time.sleep(1.0)
    assert seen_dead >= 1, "rank 2 died but num_dead_node stayed 0"
    print("KILL-WORKER %d OK (dead=%d)" % (kv.rank, seen_dead))
    sys.stdout.flush()
    # skip jax.distributed's atexit shutdown barrier: it needs EVERY
    # task to check in, and rank 2 is dead — exactly the condition this
    # test creates — so a clean interpreter exit would SIGABRT on the
    # unreachable barrier.  The assertion above is the test.
    os._exit(0)


def main_elastic():
    import jax
    from mxnet_tpu import flight_recorder, kvstore, telemetry
    from mxnet_tpu.parallel import chaos
    from mxnet_tpu.parallel.elastic import ElasticContext

    kv = kvstore.create("dist_sync")
    rank = kv.rank
    # align this rank's journal onto rank 0's wall clock so the parent
    # can merge every survivor's export into ONE de-skewed timeline
    from jax._src import distributed as _dist
    client = getattr(_dist.global_state, "client", None)
    if client is not None:
        telemetry.sync_clock(client, rank)
    chaos.install_from_env(rank=rank)
    step, batch = _build_step()
    ctx = ElasticContext(step, kvstore=kv,
                         liveness=lambda: kv.num_dead_node(timeout=1),
                         world_size=kv.num_workers)

    losses = []
    detected = None
    deadline = time.time() + 90
    i = 0
    while time.time() < deadline:
        chaos.maybe_kill(step=i, rank=rank)     # rank 2 dies mid-epoch
        losses.append(float(step(*batch).asscalar()))
        ev = ctx.maybe_recover(step=i)
        if ev is not None and ev["kind"] == "departed":
            detected = ev
            # resume mid-epoch on the re-formed mesh: a few more
            # steps, still converging
            for j in range(3):
                losses.append(float(step(*batch).asscalar()))
            break
        i += 1
        time.sleep(0.25)

    assert detected is not None, "survivor never detected the departure"
    assert detected["world_to"] == detected["world_from"] - 1
    assert losses[-1] < losses[0], "loss stopped decreasing: %r" % losses
    events = telemetry.snapshot(events=256)["events"]
    kinds = {(e["kind"], e["name"]) for e in events}
    assert ("elastic", "detect") in kinds
    assert ("elastic", "reshard") in kinds
    spans = {e["name"] for e in events if e["kind"] == "span"}
    assert {"elastic.detect", "elastic.reshard", "elastic.resume"} \
        <= spans, spans
    # the departure froze a flight-recorder bundle on this survivor
    inc_base = flight_recorder.incident_dir()
    bundles = [] if not os.path.isdir(inc_base) else \
        [d for d in os.listdir(inc_base)
         if d.startswith("incident-") and d.endswith("-elastic_departure")]
    assert bundles, "survivor dumped no elastic_departure bundle"
    # per-rank journal export for the parent's telemetry_collect merge
    out_dir = os.environ.get("MXTPU_TELEMETRY_DIR")
    if out_dir:
        telemetry.export_jsonl(
            os.path.join(out_dir, "telemetry-rank%d.jsonl" % rank))
    print("ELASTIC-WORKER %d OK (world %d->%d, loss %.4f->%.4f)"
          % (rank, detected["world_from"], detected["world_to"],
             losses[0], losses[-1]))
    sys.stdout.flush()
    # rank 2 is dead: skip the shutdown barrier; survivors leave
    # leader-last (only the live peers can write done-keys)
    _exit_ordered(kv, rank, expect_done=detected["world_to"] - 1)


def main_ckpt_phase1():
    from mxnet_tpu import checkpoint, kvstore

    kv = kvstore.create("dist_sync")
    ckpt_dir = os.environ["MXTPU_CKPT_DIR"]
    step, batch = _build_step()
    mgr = checkpoint.CheckpointManager(
        ckpt_dir, step, every_n_steps=2, rank=kv.rank,
        world_size=kv.num_workers)
    mgr.attach()
    for _ in range(6):
        step(*batch)
    assert mgr.flush(30.0), "checkpoint writer did not drain"
    if kv.rank == 0:
        man = checkpoint.read_manifest(ckpt_dir)
        assert man is not None and man["step"] == 6, man
    print("CKPT-PHASE1 %d OK" % kv.rank)
    sys.stdout.flush()
    # die abruptly — no manager close, no shutdown barrier: the
    # coordinator is "lost" and only the committed manifest survives
    # (leader-last, so peers are not SIGABRTed mid-flush)
    _exit_ordered(kv, kv.rank, expect_done=kv.num_workers - 1)


def main_ckpt_phase2():
    import numpy as onp
    from mxnet_tpu import checkpoint, kvstore

    kv = kvstore.create("dist_sync")   # the RESTARTED (smaller) job
    ckpt_dir = os.environ["MXTPU_CKPT_DIR"]
    step, batch = _build_step()
    restored = checkpoint.restore_latest(ckpt_dir, step)
    assert restored == 6, restored
    # phase 1 was deterministic (fixed seeds): recompute its 6 steps
    # fresh and the restored state must match BITWISE
    ref, _ = _build_step()
    for _ in range(6):
        ref(*batch)
    def canonical(st):
        # graph-order slots (name-sorted order flips across gluon's
        # auto-naming digit boundaries; see DataParallelStep._param_order)
        rank = {pi: k for k, pi in enumerate(st._param_order())}
        return sorted(range(len(st._opt_states)),
                      key=lambda s: rank[st._trainable[s]])

    for qa, qb in zip(canonical(ref), canonical(step)):
        for la, lb in zip(ref._materialize_slot(qa),
                          step._materialize_slot(qb)):
            onp.testing.assert_array_equal(la, lb)
    # and the restarted job keeps training
    l0 = float(step(*batch).asscalar())
    l1 = float(step(*batch).asscalar())
    assert l1 < l0
    print("CKPT-PHASE2 %d OK (restored step %d)" % (kv.rank, restored))
    sys.stdout.flush()
    os._exit(0)


def main():
    import jax
    from mxnet_tpu import parallel

    parallel.initialize()
    mode = os.environ.get("MXTPU_KILL_MODE", "liveness")
    if mode == "elastic":
        main_elastic()
    elif mode == "ckpt_phase1":
        main_ckpt_phase1()
    elif mode == "ckpt_phase2":
        main_ckpt_phase2()
    else:
        assert jax.process_count() == 3
        main_liveness()


if __name__ == "__main__":
    main()
