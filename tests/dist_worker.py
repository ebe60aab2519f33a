"""Worker body for the multi-process dist-sync kvstore test.

Spawned by tools/launch.py local mode (see tests/test_dist_multiprocess.py)
— the analogue of the reference's nightly dist fixture
(``tests/nightly/dist_sync_kvstore.py:30-60``): every worker pushes a
rank-dependent gradient and asserts the pulled aggregate bit-matches the
cross-worker sum.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp  # noqa: E402


def main():
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import kvstore, parallel

    parallel.initialize()  # from the launch.py env contract
    n = int(os.environ["MXNET_TPU_NUM_PROCESSES"])
    assert jax.process_count() == n, (jax.process_count(), n)

    kv = kvstore.create("dist_sync")
    rank = kv.rank
    assert kv.num_workers == n

    base = onp.arange(16, dtype="float32") + 1.0

    # 1) push/pull: store receives the bit-exact cross-worker sum
    kv.init("w", mx.nd.zeros((16,)))
    kv.push("w", mx.nd.array((rank + 1) * base))
    out = mx.nd.zeros((16,))
    kv.pull("w", out=out)
    expect = sum(r + 1.0 for r in range(n)) * base
    onp.testing.assert_array_equal(out.asnumpy(), expect)

    # 2) every worker observed the identical aggregate (bit-determinism)
    # — re-push the pulled value divided by n; if any worker diverged the
    # next aggregate would diverge too
    kv.push("w", mx.nd.array(out.asnumpy() / n))
    out2 = mx.nd.zeros((16,))
    kv.pull("w", out=out2)
    onp.testing.assert_array_equal(out2.asnumpy(), expect)

    # 3) updater path: running sgd-style update on the aggregated grad
    kv2 = kvstore.create("dist_sync")
    kv2.set_updater(lambda key, grad, weight:
                    weight.__isub__(0.1 * grad))
    kv2.init("p", mx.nd.ones((16,)))
    kv2.push("p", mx.nd.array(onp.full((16,), float(rank), "float32")))
    got = mx.nd.zeros((16,))
    kv2.pull("p", out=got)
    grad_sum = sum(float(r) for r in range(n))
    onp.testing.assert_allclose(got.asnumpy(),
                                onp.full((16,), 1.0 - 0.1 * grad_sum),
                                rtol=1e-6)

    # 4) integer dtype survives the multi-process reduction
    kv3 = kvstore.create("dist_sync")
    kv3.init("i", mx.nd.zeros((4,)).astype("int32"))
    kv3.push("i", mx.nd.array(onp.full((4,), rank + 1, "int32")))
    iout = mx.nd.zeros((4,)).astype("int32")
    kv3.pull("i", out=iout)
    assert str(iout.dtype) == "int32", iout.dtype
    onp.testing.assert_array_equal(
        iout.asnumpy(), onp.full((4,), sum(r + 1 for r in range(n)), "int32"))

    # 5) wire-compressed push: the cross-process collective carries the
    # PACKED 2-bit payload (reference gradient_compression.h:38-132 on the
    # kvstore_dist.h:361 push path), and the aggregate matches
    # error-feedback quantization semantics on every rank
    t = 0.5

    def q2(d):
        q = onp.where(d >= t, t, onp.where(d <= -t, -t, 0.0)).astype(
            "float32")
        return q, d - q

    kv4 = kvstore.create("dist_sync")
    kv4.set_gradient_compression({"type": "2bit", "threshold": t})
    size = 1600
    kv4.init("c", mx.nd.zeros((size,)))
    grads = {r: onp.linspace(-1, 1, size).astype("float32") * (r + 1) / n
             for r in range(n)}
    kv4.push("c", mx.nd.array(grads[rank]))
    cout = mx.nd.zeros((size,))
    kv4.pull("c", out=cout)
    expect = onp.zeros(size, "float32")
    resid = {}
    for r in range(n):
        qr, resid[r] = q2(grads[r])
        expect += qr
    onp.testing.assert_array_equal(cout.asnumpy(), expect)

    # (a) the wire payload really was ~16x smaller than dense fp32
    ratio = kv4.last_push_dense_bytes / kv4.last_push_wire_bytes
    assert ratio >= 12.0, (kv4.last_push_wire_bytes,
                           kv4.last_push_dense_bytes)

    # (b) second push: the quantization error fed back into this round
    kv4.push("c", mx.nd.array(grads[rank]))
    kv4.pull("c", out=cout)
    expect2 = onp.zeros(size, "float32")
    for r in range(n):
        qr, _ = q2(grads[r] + resid[r])
        expect2 += qr
    onp.testing.assert_array_equal(cout.asnumpy(), expect2)

    print("DIST-WORKER %d/%d OK" % (rank, n))


if __name__ == "__main__":
    main()
