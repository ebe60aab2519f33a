"""The experts' block products' Pallas kernels (``ops/pallas_moe.py``) on
the CPU, in interpret mode: forward, ``d_rows`` and ``d_weights`` against
``take`` + ``einsum`` under autodiff at the two expert cells' widths, an
owner's consecutive blocks summed in float32, an expert that owns no block,
and the dispatch — which shapes take the kernels, with its counters and
event.  What the chip's compiler makes of the same kernels is
``tests/test_chip_compile.py``.
"""
import functools

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import context, telemetry
from mxnet_tpu.ops import moe, pallas_moe

SLOTS = 128
# expert 1 owns three consecutive blocks; experts 2 and 4 own none
OWNER = (0, 1, 1, 1, 3)
HELD = 5


def _operands(k, m, dtype, seed=0):
    rs = onp.random.RandomState(seed)
    blocks = rs.randn(len(OWNER), SLOTS, k)
    weights = rs.randn(HELD, k, m) * k ** -0.5
    dy = rs.randn(len(OWNER), SLOTS, m)
    return [jnp.asarray(a, dtype) for a in (blocks, weights, dy)] \
        + [jnp.asarray(OWNER, jnp.int32)]


def _value_and_grads(fn, blocks, weights, dy, owner):
    out, back = jax.vjp(lambda b, w: fn(b, w, owner), blocks, weights)
    return (out,) + back(dy)


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return onp.linalg.norm(got - want) / max(onp.linalg.norm(want), 1e-30)


def _kernels(blocks, weights, owner):
    return pallas_moe.block_products(blocks, weights, owner, True)


# the two cells' weights: SDAR's gate / up and down, Nemotron's up and down
_WIDTHS = [(2048, 768), (768, 2048), (2688, 1856), (1856, 2688)]
_NAMES = ("product", "d_rows", "d_weights")


@pytest.mark.parametrize("k,m", _WIDTHS)
def test_kernels_match_take_and_einsum_float32(k, m):
    """Value and both gradients at float32, five blocks of 128 slots: the
    kernels are the gathered batched product to float32 rounding."""
    args = _operands(k, m, "float32")
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(_kernels, *args)
        want = _value_and_grads(pallas_moe.einsum_block_products, *args)
    for name, a, b in zip(_NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 1e-5, (name, _rel(a, b))


@pytest.mark.parametrize("k,m", _WIDTHS)
def test_kernels_hold_the_einsums_precision_bfloat16(k, m):
    """bfloat16 operands, float32 accumulation, one rounding: against the
    float32 product of the same rounded operands every output is as near as
    the gathered batched product's, and the gradient of the expert with
    THREE consecutive blocks is nearer — their sum is taken in float32 and
    rounded once, where the plain form rounds each block's product and adds
    those."""
    args = _operands(k, m, "bfloat16", seed=1)
    exact = _value_and_grads(pallas_moe.einsum_block_products,
                             *(a.astype(jnp.float32) for a in args[:3]),
                             args[3])
    got = _value_and_grads(_kernels, *args)
    plain = _value_and_grads(pallas_moe.einsum_block_products, *args)
    for name, a, b, c in zip(_NAMES, got, plain, exact):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.bfloat16
        assert _rel(a, c) < 4e-3, (name, _rel(a, c))
        assert _rel(a, c) <= 1.05 * _rel(b, c), name
    assert _rel(got[2][1], exact[2][1]) < _rel(plain[2][1], exact[2][1])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_an_expert_that_owns_no_block_gets_an_exactly_zero_gradient(dtype):
    """No grid step visits the rows of experts 2 and 4.  An output that no
    step writes keeps what its buffer held — in interpret mode NaN — so the
    gradient's buffer starts as zeros: exactly zero there, and the owners'
    rows are their sums."""
    blocks, weights, dy, owner = _operands(256, 384, dtype, seed=2)
    d_weights = pallas_moe.pallas_block_weight_grad(
        blocks, dy, owner, held=HELD, dtype=weights.dtype, interpret=True)
    assert d_weights.shape == weights.shape
    assert d_weights.dtype == weights.dtype
    got = onp.asarray(d_weights.astype(jnp.float32))
    assert not onp.isnan(got).any()
    assert (got[[2, 4]] == 0).all()
    want = onp.einsum("bsk,bsm->bkm", onp.asarray(blocks, "float64"),
                      onp.asarray(dy, "float64"))
    for expert in (0, 1, 3):
        mine = [b for b, o in enumerate(OWNER) if o == expert]
        assert _rel(got[expert], want[mine].sum(0)) < 4e-3


def test_the_slots_tile_inside_a_block():
    """Tiles smaller than a block (what the planner takes at the cells'
    1,024 and 768 slots): a block's slot tiles are consecutive steps on its
    owner's tile of the gradient, opened by the owner's first and closed by
    its last."""
    blocks, weights, dy, owner = _operands(256, 384, "float32", seed=3)
    with jax.default_matmul_precision("highest"):
        want = _value_and_grads(pallas_moe.einsum_block_products, blocks,
                                weights, dy, owner)
        out = pallas_moe.pallas_block_product(
            blocks, weights, owner, tiles=(32, 128), interpret=True)
        d_rows = pallas_moe.pallas_block_product(
            dy, weights, owner, transposed=True, backward=True,
            tiles=(64, 128), interpret=True)
        d_weights = pallas_moe.pallas_block_weight_grad(
            blocks, dy, owner, held=HELD, dtype=weights.dtype,
            tiles=(32, 128, 256), interpret=True)
    for name, a, b in zip(_NAMES, (out, d_rows, d_weights), want):
        assert _rel(a, b) < 1e-5, (name, _rel(a, b))


_DISPATCH = [
    # (slots, k, m, dtype, on_tpu, shards)
    ((1024, 2048, 768, "bfloat16", True, 1), "kernel"),    # SDAR
    ((1024, 768, 2048, "bfloat16", True, 1), "kernel"),
    ((768, 2688, 1856, "bfloat16", True, 1), "kernel"),    # Nemotron
    ((768, 1856, 2688, "bfloat16", True, 1), "kernel"),
    ((1024, 2048, 768, "bfloat16", False, 1), "einsum"),   # off the chip
    ((1024, 2048, 768, "float32", True, 1), "einsum"),
    ((1024, 2048, 768, "bfloat16", True, 4), "einsum"),    # a dp mesh
    ((200, 2048, 768, "bfloat16", True, 1), "einsum"),     # slots: no tiles
    ((256, 100, 768, "bfloat16", True, 1), "einsum"),      # k: no sublanes
    ((256, 2048, 100, "bfloat16", True, 1), "einsum"),
    ((256, 65536, 768, "bfloat16", True, 1), "einsum"),    # past the VMEM
]


@pytest.mark.parametrize("case,path", _DISPATCH,
                         ids=["-".join(map(str, c)) for c, _ in _DISPATCH])
def test_dispatch_table(case, path):
    *shape, dtype, on_tpu, shards = case
    assert pallas_moe.moe_product_dispatch(
        *shape, dtype, on_tpu=on_tpu, shards=shards) == path


@pytest.mark.parametrize("k,m", _WIDTHS)
def test_planned_tiles_fit_the_vmem_budget(k, m):
    """At both cells' slots the three products' planned working sets are
    inside ``_VMEM_CLAMP``; the contraction is whole, a width that is no
    whole number of lane tiles is tiled only where nothing contracts it."""
    slots = 1024 if 2048 in (k, m) else 768
    for contract, width in ((k, m), (m, k)):
        tiles = pallas_moe._product_tiles(slots, contract, width, 2)
        assert pallas_moe._product_vmem(tiles, contract, 2) \
            <= pallas_moe._VMEM_CLAMP
        assert slots % tiles[0] == 0 and tiles[1] % 128 == 0
    tiles = pallas_moe._grad_tiles(slots, k, m, 2)
    assert pallas_moe._grad_vmem(tiles, 2) <= pallas_moe._VMEM_CLAMP
    assert slots % tiles[0] == 0


def _kernels_on_the_cpu(monkeypatch):
    """The dispatch as on a TPU, the kernels in interpret mode."""
    real = pallas_moe.block_products
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    monkeypatch.setattr(pallas_moe, "block_products",
                        lambda *a: real(*a, True))


def test_grouped_matmul_counts_the_path_it_took(monkeypatch):
    """At trace time, once a traced shape: ``moe.product.kernel`` or
    ``moe.product.einsum``, and the ``moe.product`` event."""
    _kernels_on_the_cpu(monkeypatch)

    def trace(slots, dtype):
        counts = {p: telemetry.counter("moe.product.%s" % p)
                  for p in ("kernel", "einsum")}
        blocks = jnp.zeros((4, slots, 64), dtype)
        weights = jnp.zeros((3, 64, 48), dtype)
        owner = jnp.asarray([0, 0, 2, 2], jnp.int32)
        jax.jit(moe.grouped_matmul).lower(blocks, weights, owner)
        event = [e for e in telemetry.snapshot(events=256)["events"]
                 if e["kind"] == "moe.product"][-1]
        return {p: telemetry.counter("moe.product.%s" % p) - n
                for p, n in counts.items()}, event

    bumped, event = trace(128, "bfloat16")
    assert bumped == {"kernel": 1, "einsum": 0}
    assert (event["path"], event["name"], event["blocks"], event["width"],
            event["k"], event["m"], event["held"]) \
        == ("kernel", "kernel", 4, 128, 64, 48, 3)
    # 100 slots are no lane tile: the gathered product, on the chip too
    bumped, event = trace(100, "bfloat16")
    assert bumped == {"kernel": 0, "einsum": 1}
    assert event["path"] == "einsum"
    bumped, _ = trace(128, "float32")
    assert bumped == {"kernel": 0, "einsum": 1}


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "mlp"])
def test_the_expert_layer_on_the_kernels_is_the_layer_on_einsums(
        monkeypatch, gated):
    """``sparse_ffn`` on its blocks side, bfloat16, 4 of 32 experts held
    and 4 routes a token: the layer's output and the cotangents of the
    tokens, the gates and every weight through the kernels against the
    same call on the gathered products; one held expert gets no route."""
    n, k, experts, held, units, hidden = 1024, 4, 32, 4, 32, 48
    rs = onp.random.RandomState(5)
    allowed = onp.array([e for e in range(experts) if e != 2])
    expert = jnp.asarray(onp.stack([rs.choice(allowed, k, replace=False)
                                    for _ in range(n)]), jnp.int32)
    gate = jnp.asarray(rs.rand(n, k) + 0.1, jnp.bfloat16)
    x = jnp.asarray(rs.randn(n, units), jnp.bfloat16)
    g = jnp.asarray(rs.randn(n, units), jnp.float32)
    shapes = [(held, units, hidden)] * (2 if gated else 1) \
        + [(held, hidden, units)]
    weights = tuple(jnp.asarray(rs.randn(*s) * 0.3, jnp.bfloat16)
                    for s in shapes)
    assert moe.plan_blocks(n * k, held, experts) == (8, 256)

    def layer(x, gate, weights):
        ffn = moe.gated_experts(*weights) if gated else moe.mlp_experts(
            *weights, functools.partial(moe._activation, act_type="relu2"))
        y, sizes = moe.sparse_ffn(x, expert, gate, ffn, 0, held, experts)
        return jnp.sum(y.astype(jnp.float32) * g), (y, sizes)

    def run():
        before = telemetry.counter("moe.product.kernel")
        grads, (y, sizes) = jax.grad(layer, argnums=(0, 1, 2),
                                     has_aux=True)(x, gate, weights)
        return (y,) + tuple(jax.tree_util.tree_leaves(grads)), sizes, \
            telemetry.counter("moe.product.kernel") - before

    want, sizes, traced = run()
    assert traced == 0 and int(sizes[2]) == 0
    _kernels_on_the_cpu(monkeypatch)
    got, _, traced = run()
    assert traced > 0
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert _rel(a, b) < 1e-2, _rel(a, b)
    # the weights' gradient of the expert no token chose
    for d_weight in got[3:]:
        assert not onp.asarray(d_weight[2].astype(jnp.float32)).any()
