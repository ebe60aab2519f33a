"""Worker body for the hybrid-topology distributed test: 2 processes x 4
virtual CPU devices each — the DCN (process boundary) x ICI (intra-process)
shape of a real multi-host pod, exercised exactly as ``tools/launch.py``
spawns real workers (reference fixture ``tools/launch.py:101-116`` local
mode; capability parity with the reference's multi-machine + multi-GPU
``dist_sync`` topology, ``docs/faq/distributed_training.md``).

Covers, on a global 2x4 ``(dp, tp)`` mesh:
  1. bit-exact hybrid aggregation — a jitted loss/grad step whose batch is
     sharded over BOTH axes; integer-valued data makes every summation
     order exact, so the asserted equality is bitwise;
  2. ring attention over a process-spanning ``sp`` axis (the ppermute ring
     crosses DCN twice per rotation);
  3. a GPipe pipeline whose ``pp`` axis is the process boundary (stage 0
     on host 0, stage 1 on host 1) with a 4-wide secondary axis.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu import parallel

    parallel.initialize()
    assert jax.process_count() == 2, jax.process_count()
    devs = jax.devices()
    assert len(devs) == 8, len(devs)
    # rows = processes (DCN), columns = local devices (ICI)
    grid = onp.array(devs).reshape(2, 4)
    assert all(d.process_index == r for r in range(2) for d in grid[r]), \
        "device order does not group by process"
    mesh = Mesh(grid, ("dp", "tp"))

    def make_global(np_arr, spec):
        sh = NamedSharding(mesh, spec)
        return jax.make_array_from_callback(
            np_arr.shape, sh, lambda idx: np_arr[idx])

    # ---- 1) hybrid-sharded grad step, bitwise-exact ----------------------
    rs = onp.random.RandomState(0)
    X = rs.randint(-3, 4, (16, 8)).astype("float32")   # ints: exact sums
    Y = rs.randint(-3, 4, (16,)).astype("float32")
    W = rs.randint(-2, 3, (8,)).astype("float32")
    xg = make_global(X, P(("dp", "tp"), None))          # batch over BOTH axes
    yg = make_global(Y, P(("dp", "tp")))
    wg = make_global(W, P())                            # replicated params

    @jax.jit
    def grad_step(w, x, y):
        def loss(w):
            return jnp.sum((x @ w - y) ** 2)            # exact in f32 (ints)
        return jax.grad(loss)(w)

    g = grad_step(wg, xg, yg)
    g_local = onp.asarray(
        jax.device_get(g.addressable_shards[0].data))
    g_ref = 2.0 * X.T @ (X @ W - Y)
    onp.testing.assert_array_equal(g_local, g_ref)       # BITWISE
    for sh in g.addressable_shards:                      # replica agreement
        onp.testing.assert_array_equal(onp.asarray(jax.device_get(sh.data)),
                                       g_ref)

    # ---- 2) ring attention with sp spanning the process boundary --------
    mesh_sp = Mesh(onp.array(devs), ("sp",))
    B, H, T, D = 2, 2, 64, 16                           # 8 chunks of 8
    q = rs.uniform(-1, 1, (B, H, T, D)).astype("float32")
    k = rs.uniform(-1, 1, (B, H, T, D)).astype("float32")
    v = rs.uniform(-1, 1, (B, H, T, D)).astype("float32")
    spec = P(None, None, "sp", None)
    sh_sp = NamedSharding(mesh_sp, spec)
    qg = jax.make_array_from_callback(q.shape, sh_sp, lambda i: q[i])
    kg = jax.make_array_from_callback(k.shape, sh_sp, lambda i: k[i])
    vg = jax.make_array_from_callback(v.shape, sh_sp, lambda i: v[i])

    import functools
    from mxnet_tpu.parallel.ring_attention import ring_attention
    fn = jax.jit(jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True),
        mesh=mesh_sp, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False))
    out = fn(qg, kg, vg)

    # dense causal reference, computed locally from the full arrays
    s = onp.einsum("bhqd,bhkd->bhqk", q, k) / onp.sqrt(D)
    mask = onp.tril(onp.ones((T, T), bool))
    s = onp.where(mask, s, -1e30)
    p = onp.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = onp.einsum("bhqk,bhkd->bhqd", p, v)
    for sh in out.addressable_shards:
        sl = sh.index[2]
        got = onp.asarray(jax.device_get(sh.data))
        onp.testing.assert_allclose(got, ref[:, :, sl, :], atol=2e-5,
                                    rtol=1e-4)

    # ---- 3) pipeline with pp across the DCN boundary --------------------
    from mxnet_tpu.parallel.pipeline import pipeline_train_step
    mesh_pp = Mesh(grid, ("pp", "mp"))                  # pp = processes
    n_micro, mb, dim = 4, 4, 8
    w0 = rs.uniform(-0.5, 0.5, (dim, dim)).astype("float32")
    w1 = rs.uniform(-0.5, 0.5, (dim, 1)).astype("float32")
    xs = rs.uniform(-1, 1, (n_micro, mb, dim)).astype("float32")
    ys = rs.uniform(-1, 1, (n_micro, mb, 1)).astype("float32")

    def stage0(p0, x):
        return jnp.tanh(x @ p0)

    def stage1(p1, act, y):
        return jnp.mean((act @ p1 - y) ** 2)

    def mk(npv, spec=P()):
        shd = NamedSharding(mesh_pp, spec)
        return jax.make_array_from_callback(npv.shape, shd,
                                            lambda i: npv[i])

    with mesh_pp:
        loss = pipeline_train_step(
            [stage0, stage1], (mk(w0), mk(w1)), mk(xs), mk(ys), mesh_pp)
    got = float(onp.asarray(jax.device_get(loss.addressable_shards[0].data)))
    act = onp.tanh(xs @ w0)
    want = float(onp.mean((act @ w1 - ys) ** 2))
    assert abs(got - want) < 1e-5, (got, want)

    print("HYBRID-WORKER %d/2 OK" % jax.process_index())


if __name__ == "__main__":
    main()
