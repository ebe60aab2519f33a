"""The gated delta rule's Pallas kernels (``ops/pallas_delta.py``) on the
CPU, in interpret mode: forward and every gradient against
``ops.delta_rule._chunked`` under autodiff and against the token-by-token
recurrence, the gate's bound, the float32 state on the slowest head, and
the dispatch — which shapes take the kernels, with its counters and event.
What the chip's compiler makes of the same kernels is
``tests/test_chip_compile.py``.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import context, telemetry
from mxnet_tpu.ops import delta_rule, pallas_delta
from mxnet_tpu.ops.delta_rule import (_chunked, delta_rule_chunked,
                                      delta_rule_recurrent)

CHUNK = 64
D = 128
_NAMES = ("q", "k", "v", "g", "beta")


def _inputs(batch, seq, heads, seed=0, g=None, beta=None):
    """q and k of unit length a head (as the layer's norm leaves them), v,
    the decay g in [-5, 0] (the layer's gate: ``-5 sigmoid(.)``) or the
    constant ``g``, beta in (0, 1) or the constant ``beta``."""
    rs = onp.random.RandomState(seed)
    q, k = (rs.randn(batch, seq, heads, D) for _ in range(2))
    q, k = (t / onp.linalg.norm(t, axis=-1, keepdims=True) for t in (q, k))
    v = rs.randn(batch, seq, heads, D)
    gate = -5.0 / (1 + onp.exp(-2 * rs.randn(batch, seq, heads, D)))
    b = 1 / (1 + onp.exp(-rs.randn(batch, seq, heads)))
    if g is not None:
        gate = onp.full_like(gate, g)
    if beta is not None:
        b = onp.full_like(b, beta)
    return [jnp.asarray(t, jnp.float32) for t in (q, k, v, gate, b)]


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return onp.linalg.norm(got - want) / max(onp.linalg.norm(want), 1e-30)


def _value_and_grads(fn, args, weight):
    return fn(*args), jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
        argnums=tuple(range(len(args))))(*args)


def _kernels(exact):
    return lambda *a: pallas_delta.delta_rule_kernels(
        *a, CHUNK, D ** -0.5, exact, True)


def _reference(*a):
    return _chunked(*a, CHUNK, D ** -0.5)


def _weight(args):
    return jnp.asarray(onp.random.RandomState(1).randn(*args[2].shape),
                       jnp.float32)


# (batch, seq, heads): an odd count of heads over two batch rows, one a
# grid step; two pairs of heads, two a grid step sharing the inverse
_SHAPES = {"three_heads_batch2": (2, 2 * CHUNK, 3),
           "four_heads": (1, 2 * CHUNK, 4)}


@pytest.mark.parametrize("shape", list(_SHAPES))
def test_kernels_match_the_chunked_form_float32(shape):
    """Every product at ``HIGHEST``: the value and the gradient of EVERY
    input are the chunked form's to float32 rounding, and the value the
    recurrence's."""
    args = _inputs(*_SHAPES[shape])
    weight = _weight(args)
    got, g_got = _value_and_grads(_kernels(True), args, weight)
    want, g_want = _value_and_grads(_reference, args, weight)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert _rel(got, want) < 1e-5
    assert _rel(got, delta_rule_recurrent(*args)) < 1e-5
    for name, a, b in zip(_NAMES, g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 1e-4, (name, _rel(a, b))


def test_kernels_hold_the_chunked_forms_precision(monkeypatch):
    """At the chip's precision (the chunked form's products as the v5e
    compiles them: operands rounded to bfloat16, float32 accumulation; the
    solve at ``HIGHEST``) the kernels' value and every gradient are no
    further from the float32 chunked form than the chunked form with its
    ``einsum`` operands rounded the same way (on this data 0.4% on the
    value and the gradients, 5.6% on g's: the difference of the two sides
    of every decay, summed backwards over the chunk)."""
    args = _inputs(1, 2 * CHUNK, 2, seed=4)
    weight = _weight(args)
    want, g_want = _value_and_grads(_reference, args, weight)
    got, g_got = _value_and_grads(_kernels(False), args, weight)
    einsum = jnp.einsum

    def rounded(spec, *operands, **kw):
        return einsum(spec, *(o.astype(jnp.bfloat16) for o in operands),
                      **kw)
    monkeypatch.setattr(jnp, "einsum", rounded)
    ref, g_ref = _value_and_grads(_reference, args, weight)
    assert _rel(got, want) <= max(1.2 * _rel(ref, want), 1e-3)
    for name, a, r, w in zip(_NAMES, g_got, g_ref, g_want):
        assert _rel(a, w) <= max(1.2 * _rel(r, w), 1e-3), \
            (name, _rel(a, w), _rel(r, w))


def test_the_gate_at_its_bound_throughout():
    """g = -5 at every step and channel: a sub-chunk spans exactly
    exp(80), the most the rule's guard allows, and a chunk exp(320) —
    nothing overflows, and value and every gradient are the recurrence's
    (float32, token by token: 1.5e-7 from a float64 one).  g's gradient is
    the kernels' to 1e-4 (8e-5 from the float64 recurrence) where the
    chunked form under autodiff is 1.6% off: the decays of a sub-chunk's
    queries that fall to float32's subnormals 17-20 steps past it lose
    their digits in its differences, not in the kernels' sums."""
    args = _inputs(1, 2 * CHUNK, 2, seed=6, g=-5.0)
    weight = _weight(args)
    got, g_got = _value_and_grads(_kernels(True), args, weight)
    with jax.default_matmul_precision("highest"):
        want, g_want = _value_and_grads(delta_rule_recurrent, args, weight)
    assert bool(jnp.isfinite(got).all())
    assert _rel(got, want) < 1e-5
    for name, a, b in zip(_NAMES, g_got, g_want):
        assert bool(jnp.isfinite(a).all()), name
        assert _rel(a, b) < 2e-4, (name, _rel(a, b))
    _, g_chunked = _value_and_grads(_reference, args, weight)
    assert _rel(g_chunked[3], g_want[3]) > 10 * _rel(g_got[3], g_want[3])


def test_the_slowest_head_keeps_a_float32_state_over_512_steps():
    """A head that never decays (g = 0) and writes a fiftieth of each
    step (beta = 0.02) over 512 steps — eight chunks through the carried
    VMEM state — beside a decaying one: within 1e-5 of the recurrence,
    step by step (a bfloat16 state would lose the small writes: ~1e-3)."""
    args = _inputs(1, 512, 2, seed=2)
    slow = [a.at[:, :, 0].set(0.0) if i == 3 else a
            for i, a in enumerate(args)]
    slow[4] = slow[4].at[:, :, 0].set(0.02)
    with jax.default_matmul_precision("highest"):
        want = delta_rule_recurrent(*slow)
    got = _kernels(True)(*slow)
    assert float(jnp.abs(got - want).max()) \
        <= 1e-5 * float(jnp.abs(want).max())


def test_the_forward_writes_the_state_before_every_chunk():
    """``pallas_delta_fwd``'s second output is the state BEFORE each chunk,
    transposed (d_v, d_k): zero before the first, and before chunk c the
    recurrence's state after step c * C - 1."""
    args = _inputs(1, 2 * CHUNK, 1, seed=8)
    _, states = pallas_delta.pallas_delta_fwd(*args, CHUNK, D ** -0.5,
                                              exact=True, interpret=True)
    assert states.shape == (1, 1, 2, D, D) and states.dtype == jnp.float32
    assert float(jnp.abs(states[0, 0, 0]).max()) == 0.0
    q, k, v, g, beta = (onp.asarray(a[0, :CHUNK, 0], "float64")
                        for a in args)
    state = onp.zeros((D, D))
    for t in range(CHUNK):
        state = onp.exp(g[t])[:, None] * state
        state += beta[t] * onp.outer(k[t], v[t] - k[t] @ state)
    assert _rel(states[0, 0, 1], state.T) < 1e-5


@pytest.mark.parametrize("seq", [100, 40])
def test_a_padded_sequence_through_the_operator(monkeypatch, seq):
    """S = 100 is padded to two chunks inside ``delta_rule_chunked`` and
    cut again; S = 40 is one chunk of 48 (whole sub-chunks): value and
    every gradient on the kernel path against the same call on the chunked
    path."""
    args = _inputs(1, seq, 2, seed=3)
    weight = _weight(args)

    def rule(*a):
        return delta_rule_chunked(*a, chunk=CHUNK, lower_bound=-5.0)

    def kernel_traces():
        return telemetry.counter("linear_attention.scan.kernel")

    before = kernel_traces()
    want, g_want = _value_and_grads(rule, args, weight)
    assert kernel_traces() == before        # here: the chunked form
    _kernels_on_the_cpu(monkeypatch, exact=True)
    got, g_got = _value_and_grads(rule, args, weight)
    assert kernel_traces() > before
    assert got.shape == args[2].shape and got.dtype == want.dtype
    assert _rel(got, want) < 1e-5
    for name, a, b in zip(_NAMES, g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 1e-4, (name, _rel(a, b))


def test_the_kernels_run_once_a_batch_shard_under_a_dp_mesh():
    """Inside a program whose batch GSPMD shards (``DataParallelStep``'s
    scope, here two CPU devices) each kernel call runs a shard at a time
    (``per_batch_shard``): value and every gradient as without the
    mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.mesh import batch_sharded_over

    args = _inputs(2, CHUNK, 1, seed=5)
    weight = _weight(args)

    def program(*a):
        return _value_and_grads(_kernels(True), a, weight)

    mesh = Mesh(onp.array(jax.devices()[:2]), ("dp",))
    placed = [jax.device_put(a, NamedSharding(mesh, P("dp"))) for a in args]
    want, g_want = jax.jit(program)(*args)

    def sharded(*a):
        with batch_sharded_over(mesh):
            return program(*a)
    got, g_got = jax.jit(sharded)(*placed)
    assert _rel(got, want) < 1e-6
    for name, a, b in zip(_NAMES, g_got, g_want):
        assert a.shape == b.shape, name
        assert _rel(a, b) < 1e-6, (name, _rel(a, b))


# (seq, chunk, heads, head_dim, on the chip, v_dim) -> path
_DISPATCH = [
    ((4096, 64, 8, 128, True, None), "kernel"),      # a Ling head group
    ((4096, 64, 32, 128, True, None), "kernel"),     # all 32 heads at once
    ((128, 16, 1, 128, True, None), "kernel"),       # chunks of one sub-chunk
    ((256, 128, 2, 128, True, None), "kernel"),
    ((4096, 64, 8, 128, False, None), "chunked"),    # not on the chip
    ((64, 16, 4, 16, True, None), "chunked"),        # the tests' toys
    ((256, 64, 4, 64, True, None), "chunked"),       # a head of 64 lanes
    ((256, 64, 4, 256, True, None), "chunked"),      # two lane blocks a head
    ((256, 64, 4, 128, True, 64), "chunked"),        # v of 64
    ((200, 64, 4, 128, True, None), "chunked"),      # no whole chunks
    ((240, 40, 4, 128, True, None), "chunked"),      # chunk no sub-chunks
    ((4096, 512, 4, 128, True, None), "chunked"),    # past the VMEM budget
]


@pytest.mark.parametrize("case,path", _DISPATCH,
                         ids=["-".join(map(str, c)) for c, _ in _DISPATCH])
def test_dispatch_table(case, path):
    *shape, on_tpu, v_dim = case
    assert pallas_delta.delta_dispatch(*shape, "float32", on_tpu=on_tpu,
                                       v_dim=v_dim) == path


def _kernels_on_the_cpu(monkeypatch, exact=False):
    """``delta_rule_chunked`` as it dispatches on the chip, its kernels in
    interpret mode: the platform probe answers TPU, the kernel entry gets
    ``interpret=True``."""
    real = pallas_delta.delta_rule_kernels
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    monkeypatch.setattr(pallas_delta, "delta_rule_kernels",
                        lambda *a: real(*a, exact, True))


def test_the_operator_counts_the_path_it_took(monkeypatch):
    """At trace time, once a traced call: ``linear_attention.scan.kernel``
    or ``.chunked``, and the ``linear_attention.scan`` event's ``path``."""
    _kernels_on_the_cpu(monkeypatch)

    def trace(args, chunk=CHUNK):
        counts = {p: telemetry.counter("linear_attention.scan.%s" % p)
                  for p in ("kernel", "chunked")}
        jax.jit(lambda *a: delta_rule_chunked(*a, chunk=chunk)).lower(*args)
        event = [e for e in telemetry.snapshot(events=256)["events"]
                 if e["kind"] == "linear_attention.scan"][-1]
        return {p: telemetry.counter("linear_attention.scan.%s" % p) - n
                for p, n in counts.items()}, event

    bumped, event = trace(_inputs(1, 100, 2))
    assert bumped == {"kernel": 1, "chunked": 0}
    assert (event["path"], event["name"], event["seq_len"], event["chunk"],
            event["head_dim"]) == ("kernel", "kernel", 100, CHUNK, D)
    # a head of 64 lanes is no lane block: the chunked form, on the chip too
    bumped, event = trace([a[..., :64] if a.ndim == 4 else a
                           for a in _inputs(1, 100, 2)])
    assert bumped == {"kernel": 0, "chunked": 1}
    assert event["path"] == "chunked"
    assert delta_rule._SUBCHUNK == pallas_delta._SUBCHUNK
