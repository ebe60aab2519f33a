"""The Mamba-2 scan's Pallas kernels (``ops/pallas_ssd.py``) on the CPU, in
interpret mode: forward and every gradient against ``ops.ssm._chunked``
under autodiff, the float32 state on the slowest head, and the dispatch —
which shapes take the kernels, with its counters and event.  What the
chip's compiler makes of the same kernels is ``tests/test_chip_compile.py``.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import context, telemetry
from mxnet_tpu.ops import pallas_ssd
from mxnet_tpu.ops.ssm import _chunked, ssd_chunk_scan

CHUNK = 128


def _inputs(batch, seq, groups, per_group, head_dim, state, dtype, seed=0):
    rs = onp.random.RandomState(seed)
    heads = groups * per_group
    shapes = [(batch, seq, heads, head_dim), (batch, seq, heads), (heads,),
              (batch, seq, groups, state), (batch, seq, groups, state),
              (heads,), (heads,)]
    # the activations in ``dtype``; a_log, D and dt_bias stay float32, as
    # ``Mamba2Mixer.cast`` keeps them
    return [jnp.asarray(rs.randn(*s) * (0.5 if i == 2 else 1.0),
                        "float32" if i in (2, 5, 6) else dtype)
            for i, s in enumerate(shapes)]


def _reference(x, dt, a_log, b, c, d_skip, dt_bias, chunk=CHUNK):
    """``_chunked`` on a whole number of chunks, heads grouped as
    ``ssd_chunk_scan`` groups them."""
    bt, s, h, p = x.shape
    g = b.shape[2]

    def grouped(t):
        return t.reshape(t.shape[:-1] + (g, h // g))

    return _chunked(x.reshape(bt, s, g, h // g, p), grouped(dt),
                    grouped(a_log), b, c, grouped(d_skip), grouped(dt_bias),
                    chunk).reshape(x.shape)


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return onp.linalg.norm(got - want) / max(onp.linalg.norm(want), 1e-30)


def _value_and_grads(fn, args, weight):
    every = tuple(range(len(args)))
    return fn(*args), jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
        argnums=every)(*args)


_NAMES = ("x", "dt", "a_log", "b", "c", "d_skip", "dt_bias")
# (groups, heads a group, head width, state, chunk): R*P = 128 (two 64-wide
# heads a lane block), 512 (the cell's group), one 128-wide head a block,
# and the other sizes the dispatch lets through: state and chunk of 256
_SHAPES = {"rp128": (2, 2, 64, 128, CHUNK), "rp512": (1, 8, 64, 128, CHUNK),
           "p128": (1, 1, 128, 128, CHUNK),
           "n256_chunk256": (1, 2, 64, 256, 256)}


@pytest.mark.parametrize("shape", list(_SHAPES))
def test_kernels_match_the_chunked_form_float32(shape):
    """Value and the gradient of EVERY input at float32, batch 2, two
    chunks: the kernels are the chunked form to float32 rounding."""
    groups, per_group, head_dim, state, chunk = _SHAPES[shape]
    args = _inputs(2, 2 * chunk, groups, per_group, head_dim, state,
                   "float32")
    weight = jnp.asarray(onp.random.RandomState(1).randn(*args[0].shape),
                         jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, g_got = _value_and_grads(
            lambda *a: pallas_ssd.ssd_scan_kernels(*a, chunk, True), args,
            weight)
        want, g_want = _value_and_grads(
            lambda *a: _reference(*a, chunk=chunk), args, weight)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert _rel(got, want) < 1e-6
    for name, a, b in zip(_NAMES, g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 2e-4, (name, _rel(a, b))


@pytest.mark.parametrize("shape", ["rp128", "rp512"])
def test_kernels_hold_the_chunked_forms_precision_bfloat16(shape):
    """bfloat16 activations (float32 per-head vectors): the kernels and
    the chunked form both against the chunked form in float32 on the SAME
    rounded inputs — the kernels' value and every gradient are no further
    from it than twice the chunked form's own distance (both round their
    products' operands to bfloat16 and keep decays, cumulative sums and
    states float32; a kernel that rounded one of those would be off by
    tens of percent on a_log and dt_bias, whose gradients are differences
    of large sums)."""
    groups, per_group, head_dim, state, _ = _SHAPES[shape]
    args = _inputs(2, 2 * CHUNK, groups, per_group, head_dim, state,
                   "bfloat16")
    exact = [a.astype(jnp.float32) for a in args]
    weight = jnp.asarray(onp.random.RandomState(1).randn(*args[0].shape),
                         jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, g_got = _value_and_grads(
            lambda *a: pallas_ssd.ssd_scan_kernels(*a, CHUNK, True), args,
            weight)
        ref, g_ref = _value_and_grads(_reference, args, weight)
        want, g_want = _value_and_grads(_reference, exact, weight)
    assert got.dtype == jnp.bfloat16
    assert _rel(got, want) < max(2 * _rel(ref, want), 5e-3)
    for name, a, r, w in zip(_NAMES, g_got, g_ref, g_want):
        assert a.dtype == r.dtype, name
        assert _rel(a, w) < max(2 * _rel(r, w), 5e-3), \
            (name, _rel(a, w), _rel(r, w))


def _kernels_on_the_cpu(monkeypatch):
    """``ssd_chunk_scan`` as it dispatches on the chip, its kernels in
    interpret mode: the platform probe answers TPU, the kernel entry gets
    ``interpret=True``."""
    real = pallas_ssd.ssd_scan_kernels
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    monkeypatch.setattr(pallas_ssd, "ssd_scan_kernels",
                        lambda *a: real(*a, True))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_padded_sequence_through_the_operator(monkeypatch, dtype):
    """S = 200 is padded to two chunks inside the operator and cut again:
    value and every gradient through ``ssd_chunk_scan`` on the kernel path
    against the same call on the chunked path, batch 2."""
    args = _inputs(2, 200, 2, 2, 64, 128, dtype, seed=3)
    weight = jnp.asarray(onp.random.RandomState(1).randn(*args[0].shape),
                         jnp.float32)

    def scan(*a):
        return ssd_chunk_scan(*a, chunk=CHUNK)

    def kernel_traces():
        return telemetry.counter("ssm.scan.kernel")

    before = kernel_traces()
    with jax.default_matmul_precision("highest"):
        want, g_want = _value_and_grads(scan, args, weight)
        assert kernel_traces() == before        # here: the chunked form
        _kernels_on_the_cpu(monkeypatch)
        got, g_got = _value_and_grads(scan, args, weight)
    assert kernel_traces() > before
    tol = 2e-4 if dtype == "float32" else 2e-2
    assert got.shape == args[0].shape and got.dtype == want.dtype
    assert _rel(got, want) < tol
    for name, a, b in zip(_NAMES, g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < tol, (name, _rel(a, b))


def test_the_kernels_run_once_a_batch_shard_under_a_dp_mesh():
    """Inside a program whose batch GSPMD shards (``DataParallelStep``'s
    scope, here two CPU devices), the kernel calls are wrapped a shard
    (``per_batch_shard``: a Mosaic kernel cannot be partitioned
    automatically): value and every gradient as without the mesh — the
    per-head vectors' gradients summed over the shards."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.mesh import batch_sharded_over

    args = _inputs(2, 2 * CHUNK, 2, 2, 64, 128, "float32", seed=5)
    weight = jnp.asarray(onp.random.RandomState(1).randn(*args[0].shape),
                         jnp.float32)

    def program(*a):
        return _value_and_grads(
            lambda *b: pallas_ssd.ssd_scan_kernels(*b, CHUNK, True), a,
            weight)

    mesh = Mesh(onp.array(jax.devices()[:2]), ("dp",))
    placed = [jax.device_put(a, NamedSharding(
        mesh, P("dp") if a.ndim > 1 else P())) for a in args]
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.jit(program)(*args)

        def sharded(*a):
            # the scope spans the backward's trace too, as in the step
            with batch_sharded_over(mesh):
                return program(*a)
        got, g_got = jax.jit(sharded)(*placed)
    assert _rel(got, want) < 1e-6
    for name, a, b in zip(_NAMES, g_got, g_want):
        assert a.shape == b.shape, name
        assert _rel(a, b) < 1e-5, (name, _rel(a, b))


def test_the_slowest_head_keeps_a_float32_state_over_8192_steps():
    """``tests/test_nemotron_h.py``'s slowest head (dt = time_step_min =
    1e-3, A = -1: a memory of a thousand steps) at S = 8,192 on a kernel
    shape — one group of two 64-wide heads, state 128, 64 chunks through
    the carried VMEM state: float32 within 1e-4 of the recurrence, step by
    step."""
    from test_nemotron_h import _recurrence

    rs = onp.random.RandomState(2)
    seq, heads, p, n = 8192, 2, 64, 128
    x = jnp.asarray(rs.randn(1, seq, heads, p), jnp.float32)
    b, c = (jnp.asarray(rs.randn(1, seq, 1, n), jnp.float32)
            for _ in range(2))
    dt0 = onp.full((heads,), 1e-3)
    dt_bias = jnp.asarray(dt0 + onp.log(-onp.expm1(-dt0)), jnp.float32)
    args = [x, jnp.zeros((1, seq, heads)), jnp.zeros((heads,)), b, c,
            jnp.zeros((heads,)), dt_bias]
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*args)
        got = pallas_ssd.ssd_scan_kernels(*args, CHUNK, True)
    assert float(jnp.abs(got - want).max()) \
        <= 1e-4 * float(jnp.abs(want).max())


# (seq, chunk, heads, head_dim, groups, state, on the chip) -> path
_DISPATCH = [
    ((8192, 128, 64, 64, 8, 128, True), "kernel"),     # the cell
    ((256, 128, 4, 64, 2, 128, True), "kernel"),
    ((256, 128, 1, 128, 1, 128, True), "kernel"),      # one 128-wide head
    ((512, 256, 8, 64, 1, 256, True), "kernel"),
    ((8192, 128, 64, 64, 8, 128, False), "chunked"),   # not on the chip
    ((32, 8, 4, 8, 2, 16, True), "chunked"),           # the tests' toys
    ((256, 128, 2, 32, 2, 128, True), "chunked"),      # R*P = 64 lanes
    ((256, 128, 4, 48, 1, 128, True), "chunked"),      # 48 no divisor of 128
    ((256, 128, 4, 64, 2, 64, True), "chunked"),       # N = 64
    ((192, 64, 4, 64, 2, 128, True), "chunked"),       # chunk 64
    ((4096, 1024, 64, 64, 8, 128, True), "chunked"),   # past the VMEM budget
]


@pytest.mark.parametrize("case,path", _DISPATCH,
                         ids=["-".join(map(str, c)) for c, _ in _DISPATCH])
def test_dispatch_table(case, path):
    *shape, on_tpu = case
    assert pallas_ssd.ssd_dispatch(*shape, "bfloat16", on_tpu=on_tpu) == path


def test_the_operator_counts_the_path_it_took(monkeypatch):
    """At trace time, once a traced shape: ``ssm.scan.kernel`` or
    ``ssm.scan.chunked``, and the ``ssm.scan`` event's ``path``."""
    _kernels_on_the_cpu(monkeypatch)

    def trace(args):
        counts = {p: telemetry.counter("ssm.scan.%s" % p)
                  for p in ("kernel", "chunked")}
        jax.jit(lambda *a: ssd_chunk_scan(*a, chunk=CHUNK)).lower(*args)
        event = [e for e in telemetry.snapshot(events=256)["events"]
                 if e["kind"] == "ssm.scan"][-1]
        return {p: telemetry.counter("ssm.scan.%s" % p) - n
                for p, n in counts.items()}, event

    bumped, event = trace(_inputs(1, 200, 2, 2, 64, 128, "bfloat16"))
    assert bumped == {"kernel": 1, "chunked": 0}
    assert (event["path"], event["name"], event["seq_len"], event["padded"],
            event["head_dim"]) == ("kernel", "kernel", 200, True, 64)
    # a state of 16 is no lane block: the chunked form, on the chip too
    bumped, event = trace(_inputs(1, 200, 2, 2, 64, 16, "bfloat16"))
    assert bumped == {"kernel": 0, "chunked": 1}
    assert event["path"] == "chunked"
