"""Flash-attention op + Pallas kernel tests (CPU: interpret mode / jnp
fallback; the same kernels compile for a described TPU in
``test_chip_compile.py``).

Reference capability: ``src/operator/contrib/transformer.cc``
(interleaved matmul self-attention pipeline).
"""
import numpy as onp
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import pallas_attention as P


def _dense(q, k, v, causal=False, scale=None):
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = s.shape[-2:]
        mask = onp.tril(onp.ones((tq, tk), bool), tk - tq)
        s = jnp.where(jnp.asarray(mask), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def _rand(shape, seed):
    return jnp.asarray(
        onp.random.RandomState(seed).uniform(-1, 1, shape).astype("float32"))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 3, 256, 64), (1, 1, 200, 48)])
def test_pallas_fwd_kernel_matches_dense(causal, shape):
    q, k, v = (_rand(shape, i) for i in range(3))
    out, lse = P.pallas_flash_attention(
        q, k, v, causal=causal, interpret=True, return_lse=True,
        block_q=128, block_k=128)
    ref = _dense(q, k, v, causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
    # lse really is the softmax log-normalizer
    want_lse = jax.nn.logsumexp(
        (jnp.einsum("bhqd,bhkd->bhqk", q, k) * shape[-1] ** -0.5
         ).astype(jnp.float32), axis=-1)
    if not causal:
        assert float(jnp.max(jnp.abs(lse - want_lse))) < 2e-4


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_bwd_kernels_match_dense_vjp(causal):
    shape = (2, 2, 256, 64)
    q, k, v = (_rand(shape, 10 + i) for i in range(3))
    g = _rand(shape, 20)
    out, lse = P.pallas_flash_attention(
        q, k, v, causal=causal, interpret=True, return_lse=True,
        block_q=128, block_k=128)
    dq, dk, dv = P.pallas_flash_attention_bwd(
        q, k, v, out, lse, g, causal=causal, interpret=True,
        block_q=128, block_k=128)
    _, vjp = jax.vjp(lambda a, b, c: _dense(a, b, c, causal), q, k, v)
    rq, rk, rv = vjp(g)
    for got, want in ((dq, rq), (dk, rk), (dv, rv)):
        assert float(jnp.max(jnp.abs(got - want))) < 5e-5


def _dense_masked(q, k, v, kv_lens=None, q_seg=None, kv_seg=None,
                  causal=False):
    d = q.shape[-1]
    tq, tk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * d ** -0.5
    mask = jnp.ones((q.shape[0], 1, tq, tk), bool)
    if kv_lens is not None:
        mask = mask & (jnp.arange(tk)[None, None, None, :]
                       < kv_lens[:, None, None, None])
    if q_seg is not None:
        mask = mask & (q_seg[:, None, :, None] == kv_seg[:, None, None, :])
    if causal:
        mask = mask & (jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :])
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask, axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


@pytest.mark.parametrize("lens", [[256, 131], [1, 256]])
def test_pallas_kv_lens_matches_dense(lens):
    shape = (2, 2, 256, 64)
    q, k, v = (_rand(shape, 40 + i) for i in range(3))
    kv_lens = jnp.asarray(lens, jnp.int32)
    out = P.pallas_flash_attention(q, k, v, interpret=True, block_q=128,
                                   block_k=128, kv_lens=kv_lens)
    ref = _dense_masked(q, k, v, kv_lens=kv_lens)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_pallas_kv_lens_beyond_tk_clamps_to_seq_len():
    # out-of-range kv_lens (> Tk) must behave exactly like lens == Tk:
    # the length mask replaces the padded-tail mask, so without clamping
    # the zero-padded key rows would enter the online softmax
    shape = (2, 2, 200, 64)       # Tk=200 pads to 256 inside the kernel
    q, k, v = (_rand(shape, 45 + i) for i in range(3))
    out = P.pallas_flash_attention(
        q, k, v, interpret=True, block_q=128, block_k=128,
        kv_lens=jnp.asarray([500, 200], jnp.int32))
    ref = _dense_masked(q, k, v, kv_lens=jnp.asarray([200, 200], jnp.int32))
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_pallas_kv_lens_bwd_matches_dense_vjp():
    shape = (2, 2, 256, 64)
    q, k, v = (_rand(shape, 50 + i) for i in range(3))
    g = _rand(shape, 53)
    kv_lens = jnp.asarray([200, 77], jnp.int32)
    out, lse = P.pallas_flash_attention(
        q, k, v, interpret=True, return_lse=True, block_q=128, block_k=128,
        kv_lens=kv_lens)
    dq, dk, dv = P.pallas_flash_attention_bwd(
        q, k, v, out, lse, g, interpret=True, block_q=128, block_k=128,
        kv_lens=kv_lens)
    _, vjp = jax.vjp(lambda a, b, c: _dense_masked(a, b, c, kv_lens),
                     q, k, v)
    rq, rk, rv = vjp(g)
    for got, want in ((dq, rq), (dk, rk), (dv, rv)):
        assert float(jnp.max(jnp.abs(got - want))) < 5e-5
    # masked-out keys get exactly zero dk/dv (their blocks are skipped)
    assert float(jnp.max(jnp.abs(dk[1, :, 77:]))) == 0.0
    assert float(jnp.max(jnp.abs(dv[1, :, 77:]))) == 0.0


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_segment_ids_match_dense(causal):
    shape = (2, 2, 256, 32)
    q, k, v = (_rand(shape, 60 + i) for i in range(3))
    # packed sequences: two segments per row, split at different points
    seg = onp.zeros((2, 256), onp.int32)
    seg[0, 100:] = 1
    seg[1, 180:] = 1
    seg = jnp.asarray(seg)
    out = P.pallas_flash_attention(
        q, k, v, causal=causal, interpret=True, block_q=128, block_k=128,
        q_segments=seg, kv_segments=seg)
    ref = _dense_masked(q, k, v, q_seg=seg, kv_seg=seg, causal=causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_pallas_segment_ids_bwd_matches_dense_vjp():
    shape = (1, 2, 256, 32)
    q, k, v = (_rand(shape, 70 + i) for i in range(3))
    g = _rand(shape, 73)
    seg = jnp.asarray(onp.repeat([[0, 1]], 128, axis=1).reshape(1, 256))
    out, lse = P.pallas_flash_attention(
        q, k, v, interpret=True, return_lse=True, block_q=128, block_k=128,
        q_segments=seg, kv_segments=seg)
    dq, dk, dv = P.pallas_flash_attention_bwd(
        q, k, v, out, lse, g, interpret=True, block_q=128, block_k=128,
        q_segments=seg, kv_segments=seg)
    _, vjp = jax.vjp(
        lambda a, b, c: _dense_masked(a, b, c, q_seg=seg, kv_seg=seg),
        q, k, v)
    rq, rk, rv = vjp(g)
    for got, want in ((dq, rq), (dk, rk), (dv, rv)):
        assert float(jnp.max(jnp.abs(got - want))) < 5e-5


def test_fully_masked_rows_emit_zero_and_zero_grads():
    """A q row whose segment matches no key must return exactly 0 with
    zero dq, and contribute nothing to dk/dv (regression: the online
    softmax saw exp(s - m_new) == 1 when the whole row was -inf)."""
    shape = (1, 2, 128, 32)
    q, k, v = (_rand(shape, 90 + i) for i in range(3))
    g = _rand(shape, 93)
    q_seg = jnp.asarray(onp.where(onp.arange(128) < 64, 0, 7)[None, :])
    kv_seg = jnp.zeros((1, 128), jnp.int32)       # id 7 matches nothing
    out, lse = P.pallas_flash_attention(
        q, k, v, interpret=True, return_lse=True, block_q=64, block_k=64,
        q_segments=q_seg, kv_segments=kv_seg)
    assert float(jnp.max(jnp.abs(out[:, :, 64:]))) == 0.0
    assert float(jnp.max(jnp.abs(lse[:, :, 64:]))) == 0.0
    dq, dk, dv = P.pallas_flash_attention_bwd(
        q, k, v, out, lse, g, interpret=True, block_q=64, block_k=64,
        q_segments=q_seg, kv_segments=kv_seg)
    assert float(jnp.max(jnp.abs(dq[:, :, 64:]))) == 0.0
    _, vjp = jax.vjp(
        lambda a, b, c: _dense_masked(a, b, c, q_seg=q_seg, kv_seg=kv_seg),
        q, k, v)
    rq, rk, rv = vjp(g)
    for got, want in ((dq, rq), (dk, rk), (dv, rv)):
        assert float(jnp.max(jnp.abs(got - want))) < 5e-5


def test_mha_mask_plus_valid_length_combines():
    """Dense path: an explicit additive mask AND valid_length together —
    padded keys must still be excluded."""
    from mxnet_tpu.gluon.contrib.nn import MultiHeadAttention
    mx.random.seed(0)
    attn = MultiHeadAttention(units=32, num_heads=2)
    attn.initialize()
    x = mx.nd.array(onp.random.RandomState(7).uniform(
        -1, 1, (2, 48, 32)).astype("float32"))
    attn(x)
    zero_mask = mx.nd.zeros((2, 1, 1, 48))
    vl = mx.nd.array(onp.array([48, 20]), dtype="int32")
    got = attn(x, zero_mask, vl).asnumpy()
    # reference: additive mask that encodes the same padding
    add = onp.zeros((2, 1, 1, 48), "float32")
    add[1, :, :, 20:] = -1e30
    want = attn(x, mx.nd.array(add)).asnumpy()
    assert onp.abs(got[0] - want[0]).max() < 2e-5
    assert onp.abs(got[1, :20] - want[1, :20]).max() < 2e-5


def test_flash_attention_custom_vjp_masked_fallback():
    """The public custom-vjp op with kv_lens via the CPU fallback path."""
    shape = (2, 2, 128, 32)
    q, k, v = (_rand(shape, 80 + i) for i in range(3))
    kv_lens = jnp.asarray([128, 57], jnp.int32)

    def loss(q, k, v):
        return jnp.sum(P.flash_attention(q, k, v, False, None,
                                         kv_lens) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_masked(q, k, v, kv_lens=kv_lens) ** 2)

    assert float(jnp.abs(loss(q, k, v) - dense_loss(q, k, v))) < 1e-3
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for g1, g2 in zip(got, want):
        assert float(jnp.max(jnp.abs(g1 - g2))) < 5e-5


def test_transformer_valid_length_routes_flash():
    """MultiHeadAttention(valid_length=...) == explicit additive mask."""
    from mxnet_tpu.gluon.contrib.nn import MultiHeadAttention
    mx.random.seed(0)
    attn = MultiHeadAttention(units=64, num_heads=4)
    attn.initialize()
    x = mx.nd.array(onp.random.RandomState(5).uniform(
        -1, 1, (2, 96, 64)).astype("float32"))
    attn(x)  # materialize
    vl = mx.nd.array(onp.array([96, 40]), dtype="int32")
    out_flash = attn(x, None, vl)
    # dense path: additive -inf on padded keys
    add = onp.zeros((2, 1, 1, 96), "float32")
    add[1, :, :, 40:] = -1e30
    out_dense = attn(x, mx.nd.array(add))
    got = out_flash.asnumpy()
    want = out_dense.asnumpy()
    # padded q rows differ (garbage either way); compare valid rows
    assert onp.abs(got[0] - want[0]).max() < 2e-5
    assert onp.abs(got[1, :40] - want[1, :40]).max() < 2e-5


@pytest.mark.parametrize("heads,width,layout,per_block", [
    (12, 64, "bshd_pair", 2),       # BERT-base's heads: two a lane block
    (4, 32, "bhsd", 1),             # 32-wide heads stay head-major
])
def test_mha_chooses_the_layout_from_its_heads(monkeypatch, heads, width,
                                               layout, per_block):
    """``MultiHeadAttention`` with a padding mask runs the flash kernels
    (interpret mode here) in the layout its head width and count call
    for, says so in the ``attention_dispatch`` event, and gives the
    output and every parameter gradient of its own dense path under an
    all-zero additive mask."""
    import functools
    from mxnet_tpu import autograd, context, telemetry
    from mxnet_tpu.gluon.contrib.nn import MultiHeadAttention
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    for name in ("pallas_flash_attention", "pallas_flash_attention_bwd",
                 "pallas_flash_attention_bshd",
                 "pallas_flash_attention_bwd_bshd"):
        monkeypatch.setattr(P, name, functools.partial(getattr(P, name),
                                                       interpret=True))
    B, L = 2, 128
    rs = onp.random.RandomState(11)
    mx.random.seed(3)
    attn = MultiHeadAttention(units=heads * width, num_heads=heads)
    attn.initialize()
    x = mx.nd.array(rs.uniform(-1, 1, (B, L, heads * width))
                    .astype("float32"))
    weight = mx.nd.array(rs.uniform(-1, 1, (B, L, heads * width))
                         .astype("float32"))
    vl = mx.nd.array(onp.array([L, 37]), dtype="int32")
    params = [p for _, p in sorted(attn.collect_params().items())]

    def run(mask):
        with autograd.record():
            out = attn(x, mask, vl)
            loss = (out * weight).sum()
        loss.backward()
        return out.asnumpy(), [p.grad().asnumpy().copy() for p in params]

    telemetry.reset()
    out, grads = run(None)
    events = [e for e in telemetry.snapshot()["events"]
              if e["kind"] == "attention_dispatch"]
    assert events and all(e["layout"] == layout
                          and e["heads_per_block"] == per_block
                          for e in events), events
    assert telemetry.counter("attention.layout.%s" % layout) == len(events)
    want, want_grads = run(mx.nd.zeros((B, 1, 1, L)))
    assert onp.abs(out - want).max() < 2e-5
    assert len(grads) == 4
    for g, w in zip(grads, want_grads):
        assert onp.abs(g - w).max() < 2e-4 * max(1.0, onp.abs(w).max())


def test_flash_attention_op_and_grad_fallback():
    """The registered op (jnp fallback off-TPU) forward + custom-vjp grad."""
    shape = (1, 2, 128, 32)
    q, k, v = (_rand(shape, 30 + i) for i in range(3))
    out = mx.nd.flash_attention(mx.nd.from_jax(q), mx.nd.from_jax(k),
                                mx.nd.from_jax(v))
    ref = _dense(q, k, v)
    assert onp.abs(out.asnumpy() - onp.asarray(ref)).max() < 2e-5

    def loss(q, k, v):
        return jnp.sum(P.flash_attention(q, k, v) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(_dense(q, k, v) ** 2)

    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for g1, g2 in zip(got, want):
        assert float(jnp.max(jnp.abs(g1 - g2))) < 5e-5


# --- BSHD (transpose-free) layout ------------------------------------------

def _bshd(x):
    return jnp.swapaxes(x, 1, 2)


def _program(fn, *args):
    """(names of the Pallas kernels, names of every primitive) in the
    program ``fn(*args)`` traces to."""
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return ([e.params["name"] for e in eqns
             if e.primitive.name == "pallas_call"],
            {e.primitive.name for e in eqns})


# id -> (B, H, T, D, block_q, block_k, forward kernel, backward kernels)
_BSHD_CASES = {
    # an odd head count of 64-wide heads, K in two blocks: not taken in the
    # lane-block layout, the entry goes through the (B, H, T, D) kernels
    "odd_heads": (2, 3, 128, 64, 64, 64,
                  "flash_stream_fwd", ["flash_dq", "flash_dkv"]),
    # two 64-wide heads a 128-lane block, one kernel each way
    "pair": (2, 4, 128, 64, 128, 128,
             "flash_bshd_cols_fwd", ["flash_bshd_cols_dqkv"]),
    # ... T not a multiple of the block: zero rows pad T, never D
    "pair_ragged_t": (2, 2, 200, 64, 256, 256,
                      "flash_bshd_cols_fwd", ["flash_bshd_cols_dqkv"]),
    # ... two q blocks: the forward's grid walks them, the backward past
    # one q block is the (B, H, T, D) kernels'
    "pair_q_blocks": (1, 2, 256, 64, 128, 256,
                      "flash_bshd_cols_fwd", ["flash_dqkv_fused"]),
    # a head of whole lane tiles: one head a block, same kernels
    "d128": (2, 2, 128, 128, 128, 128,
             "flash_bshd_cols_fwd", ["flash_bshd_cols_dqkv"]),
    # ... K in two blocks: the streamed forward, the split backward
    "d128_stream": (1, 2, 128, 128, 64, 64,
                    "flash_bshd_stream_fwd",
                    ["flash_bshd_dq", "flash_bshd_dkv"]),
}


# kv_lens: none; a row shorter than the sequence and a full one; a row
# shorter than one block and a row of length 0 (its output and gradients
# are exactly zero)
@pytest.mark.parametrize("lens", [None, (100, 128), (40, 0)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("case", list(_BSHD_CASES))
def test_bshd_kernels_match_bhtd(case, causal, lens):
    """The (B,T,H,D)-layout kernels compute exactly what the flat-grid
    BHTD kernels do, fwd and bwd (no transposes on either side) — one
    head a lane block or two 64-wide heads a 128-lane block — and a shape
    the layout does not take runs the BHTD kernels themselves."""
    B, H, T, D, block_q, block_k, fwd_name, bwd_names = _BSHD_CASES[case]
    B = max(B, 2) if lens else B
    q, k, v = (_rand((B, H, T, D), i) for i in range(3))
    kv = jnp.asarray(lens, jnp.int32) if lens else None
    kw = dict(causal=causal, interpret=True, block_q=block_q,
              block_k=block_k, kv_lens=kv)
    o1, l1 = P.pallas_flash_attention(q, k, v, return_lse=True, **kw)
    fwd = lambda q, k, v: P.pallas_flash_attention_bshd(
        q, k, v, return_lse=True, **kw)
    o2, l2 = fwd(_bshd(q), _bshd(k), _bshd(v))
    assert _program(fwd, _bshd(q), _bshd(k), _bshd(v))[0] == [fwd_name]
    assert float(jnp.max(jnp.abs(_bshd(o2) - o1))) < 1e-6
    assert float(jnp.max(jnp.abs(l2 - l1))) < 1e-6
    if lens and 0 in lens:
        assert float(jnp.max(jnp.abs(o2[lens.index(0)]))) == 0.0
    do = _rand((B, H, T, D), 7)
    g1 = P.pallas_flash_attention_bwd(q, k, v, o1, l1, do, **kw)
    bwd = lambda q, k, v, o, l, do: P.pallas_flash_attention_bwd_bshd(
        q, k, v, o, l, do, **kw)
    operands = (_bshd(q), _bshd(k), _bshd(v), o2, l2, _bshd(do))
    g2 = bwd(*operands)
    assert _program(bwd, *operands)[0] == bwd_names
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(_bshd(b) - a))) < 5e-6


@pytest.mark.parametrize("heads,width", [(4, 64), (2, 128)])
def test_bshd_lane_blocks_build_no_padded_array(heads, width):
    """Where T divides into its blocks the lane-block kernels read the
    (B, T, H, D) operands as they are and write the results as they are
    returned: the traced program is reshapes round ONE kernel — no pad of
    the head width, no slice of an output, no transpose."""
    x = jnp.zeros((2, 128, heads, width), jnp.float32)
    lse = jnp.zeros((2, heads, 128), jnp.float32)
    kw = dict(interpret=True, block_q=128, block_k=128,
              kv_lens=jnp.asarray((100, 128), jnp.int32))
    names, prims = _program(lambda q, k, v: P.pallas_flash_attention_bshd(
        q, k, v, return_lse=True, **kw), x, x, x)
    assert names == ["flash_bshd_cols_fwd"]
    assert not prims & {"pad", "slice", "dynamic_slice", "transpose",
                        "concatenate", "gather"}, prims
    names, prims = _program(
        lambda q, k, v, o, l, do: P.pallas_flash_attention_bwd_bshd(
            q, k, v, o, l, do, **kw), x, x, x, x, lse, x)
    assert names == ["flash_bshd_cols_dqkv"]
    assert not prims & {"pad", "slice", "dynamic_slice", "transpose",
                        "concatenate", "gather", "dot_general",
                        "reduce_sum"}, prims


def test_flash_attention_bshd_fallback_grads_match_dense():
    """Off-TPU the BSHD public op runs the jnp path; grads match the
    dense oracle on transposed operands."""
    B, H, T, D = 2, 2, 64, 32
    q, k, v = (_rand((B, H, T, D), i) for i in range(3))

    def f(a, b, c):
        return jnp.sum(P.flash_attention_bshd(_bshd(a), _bshd(b),
                                              _bshd(c)).astype(jnp.float32))

    def ref(a, b, c):
        return jnp.sum(_dense(a, b, c).astype(jnp.float32))

    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-4


@pytest.mark.parametrize("config", [
    {}, {"causal": True}, {"lens": (100, 128)},
    {"segs": True}, {"causal": True, "lens": (100, 128)},
])
def test_fused_single_kblock_bwd_matches_split(config):
    """When the whole K axis fits one block the backward runs the fused
    dqkv kernel (5 dots, shared score/dp recompute); it must match the
    split dq+dkv kernels bit-for-fp32-bit across every mask config."""
    B, H, T, D = 2, 3, 128, 64
    q, k, v, do = (_rand((B, H, T, D), i) for i in range(4))
    causal = config.get("causal", False)
    kl = jnp.asarray(config["lens"], jnp.int32) if "lens" in config \
        else None
    segs = jnp.asarray(
        onp.repeat(onp.arange(4), 32)[None].repeat(B, 0), jnp.int32) \
        if config.get("segs") else None
    kw = dict(causal=causal, kv_lens=kl, q_segments=segs,
              kv_segments=segs, interpret=True, block_q=64)
    o1, l1 = P.pallas_flash_attention(q, k, v, return_lse=True,
                                      block_k=128, **kw)
    g_fused = P.pallas_flash_attention_bwd(q, k, v, o1, l1, do,
                                           block_k=128, **kw)   # n_k=1
    o2, l2 = P.pallas_flash_attention(q, k, v, return_lse=True,
                                      block_k=64, **kw)
    g_split = P.pallas_flash_attention_bwd(q, k, v, o2, l2, do,
                                           block_k=64, **kw)    # n_k=2
    for a, b in zip(g_fused, g_split):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5


# ---------------------------------------------------------------------------
# a mask given as data: q_mask [reach, own], kv_mask [rank, own]
# ---------------------------------------------------------------------------

_NEVER = 2 ** 31 - 1


def _block_diffusion_operands(length, block, drop=0):
    """A ``[clean ; noised]`` row of 2 x ``length`` tokens in blocks of
    ``block`` (``gluon.model_zoo.block_diffusion_mask`` has the rule),
    its last ``drop`` tokens cut off so that the row is ragged."""
    from mxnet_tpu.gluon.model_zoo import block_diffusion_mask
    q_mask, kv_mask = block_diffusion_mask(length, block)
    keep = 2 * length - drop
    return jnp.asarray(q_mask[:, :keep]), jnp.asarray(kv_mask[:, :keep])


def _visible(q_mask, kv_mask):
    reach, q_own = q_mask[:, :, None, 0], q_mask[:, :, None, 1]
    rank, k_own = kv_mask[:, None, :, 0], kv_mask[:, None, :, 1]
    return onp.asarray((rank <= reach) | ((k_own == q_own) & (q_own >= 0)))


def _masked_dense(q, k, v, q_mask, kv_mask):
    """Plain float32 attention under the dense mask, GQA by repetition;
    a query that sees no key gives zero."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    seen = jnp.asarray(_visible(q_mask, kv_mask))[:, None]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    p = jnp.where(seen.any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# (block_q, block_k): streamed with square and oblong tiles, one K block
# with q streamed (the fused backward), and one block in all
@pytest.mark.parametrize("blocks", [(64, 64), (64, 128), (128, 64),
                                    (64, 512), (512, 512)])
def test_masked_kernels_match_dense_fwd_and_three_gradients(blocks):
    """The mask as data in every BHSD kernel, 8 query heads on 1
    key-value head, a ragged row (padded tiles): at 64 x 64 the 6 x 6
    tiles are dead, whole and partial by turns, and the noised half's
    diagonal tiles are live in one 4 x 4 square in 256."""
    q_mask, kv_mask = _block_diffusion_operands(192, 4, drop=5)
    s = q_mask.shape[1]
    q = _rand((1, 8, s, 64), 0)
    k, v = _rand((1, 1, s, 64), 1), _rand((1, 1, s, 64), 2)
    g = _rand((1, 8, s, 64), 3)
    bq, bk = blocks
    out, lse = P.pallas_flash_attention(
        q, k, v, block_q=bq, block_k=bk, interpret=True, return_lse=True,
        q_mask=q_mask, kv_mask=kv_mask)
    want, vjp = jax.vjp(
        lambda q, k, v: _masked_dense(q, k, v, q_mask, kv_mask), q, k, v)
    assert float(jnp.abs(out - want).max()) < 2e-5
    # the op's own reference takes the same operands
    ref = P._reference_attention(q, k, v, False, None, q_mask=q_mask,
                                 kv_mask=kv_mask)
    assert float(jnp.abs(ref - want).max()) < 2e-5
    grads = P.pallas_flash_attention_bwd(
        q, k, v, out, lse, g, block_q=bq, block_k=bk, interpret=True,
        q_mask=q_mask, kv_mask=kv_mask)
    for got, exp, name in zip(grads, vjp(g), "qkv"):
        assert got.shape == exp.shape
        assert float(jnp.abs(got - exp).max()) < 5e-5, name


def test_tile_summary_is_exact_for_block_diffusion():
    """``_tile_states`` against the pairs themselves: 0 exactly where a
    tile holds no live pair, 2 exactly where all are live."""
    q_mask, kv_mask = _block_diffusion_operands(256, 4)
    qm, km = P._mask_operands(q_mask, kv_mask, 512, 512)
    seen = _visible(q_mask, kv_mask)[0]
    for bq, bk in ((64, 64), (128, 32), (32, 256)):
        states = onp.asarray(P._tile_states(qm, km, bq, bk))[0]
        tiles = seen.reshape(512 // bq, bq, 512 // bk, bk)
        some, every = tiles.any((1, 3)), tiles.all((1, 3))
        onp.testing.assert_array_equal(states > 0, some)
        onp.testing.assert_array_equal(states == 2, every)
    # 64 x 64: 8 tiles a side, 4 clean and 4 noised
    states = onp.asarray(P._tile_states(qm, km, 64, 64))[0]
    assert (states > 0).sum() == 10 + 10 + 4
    assert (states == 2).sum() == 6 + 6


def test_a_dead_tile_fetches_the_block_that_is_resident():
    live = jnp.asarray([[False, False, True, False, True, False],
                        [True, False, False, False, False, True],
                        [False] * 6])
    onp.testing.assert_array_equal(
        onp.asarray(P._resident_block(live)),
        [[2, 2, 2, 2, 4, 4], [0, 0, 0, 0, 0, 5], [0] * 6])


def test_rows_and_tiles_wholly_dead_give_zero_and_finite_gradients():
    """Queries that see nothing at all (a reach under every rank, no
    ``own``) sit in tiles that are wholly dead: they return 0, take no
    gradient, and leave the others' untouched."""
    s = 256
    pos = onp.arange(s)
    q_mask = onp.stack([onp.where(pos < 128, -1, pos), -onp.ones(s, int)],
                       -1)[None].astype("int32")
    kv_mask = onp.stack([pos, -onp.ones(s, int)], -1)[None].astype("int32")
    q_mask, kv_mask = jnp.asarray(q_mask), jnp.asarray(kv_mask)
    q, k, v, g = (_rand((1, 2, s, 64), i) for i in range(4))
    out, lse = P.pallas_flash_attention(
        q, k, v, block_q=64, block_k=64, interpret=True, return_lse=True,
        q_mask=q_mask, kv_mask=kv_mask)
    assert not onp.asarray(out[:, :, :128]).any()
    want = _dense(q, k, v, causal=True)
    assert float(jnp.abs(out[:, :, 128:] - want[:, :, 128:]).max()) < 2e-5
    dq, dk, dv = P.pallas_flash_attention_bwd(
        q, k, v, out, lse, g, block_q=64, block_k=64, interpret=True,
        q_mask=q_mask, kv_mask=kv_mask)
    assert all(bool(jnp.isfinite(x).all()) for x in (dq, dk, dv))
    assert not onp.asarray(dq[:, :, :128]).any()


def test_causal_order_and_packed_documents_as_data():
    """rank = reach = position is ``causal=True``; ``own`` = document id
    with no reach is the segment mask: the same kernels, the mask given."""
    s = 256
    pos = onp.arange(s)
    none = -onp.ones(s, int)
    q, k, v = (_rand((1, 2, s, 64), 5 + i) for i in range(3))

    def masked(q_cols, k_cols):
        return P.pallas_flash_attention(
            q, k, v, block_q=64, block_k=64, interpret=True,
            q_mask=jnp.asarray(onp.stack(q_cols, -1)[None], jnp.int32),
            kv_mask=jnp.asarray(onp.stack(k_cols, -1)[None], jnp.int32))
    causal = P.pallas_flash_attention(q, k, v, causal=True, block_q=64,
                                      block_k=64, interpret=True)
    assert float(jnp.abs(masked((pos, none), (pos, none)) - causal).max()) \
        < 2e-6
    seg = jnp.asarray((pos // 100)[None], jnp.int32)
    packed = P.pallas_flash_attention(
        q, k, v, block_q=64, block_k=64, interpret=True, q_segments=seg,
        kv_segments=seg)
    got = masked((onp.full(s, -1), pos // 100),
                 (onp.full(s, _NEVER), pos // 100))
    assert float(jnp.abs(got - packed).max()) < 2e-6


def test_mask_as_data_stands_alone():
    q_mask, kv_mask = _block_diffusion_operands(64, 4)
    q = k = v = _rand((1, 1, 128, 64), 0)
    with pytest.raises(ValueError, match="go together"):
        P.pallas_flash_attention(q, k, v, interpret=True, q_mask=q_mask)
    for other in (dict(causal=True), dict(kv_lens=jnp.asarray([100]))):
        with pytest.raises(ValueError, match="stand alone"):
            P.pallas_flash_attention(q, k, v, interpret=True, q_mask=q_mask,
                                     kv_mask=kv_mask, **other)
    with pytest.raises(ValueError, match="stand alone"):
        P.flash_attention(q, k, v, True, None, None, None, None, q_mask,
                          kv_mask)


def test_flash_attention_op_takes_the_mask_and_differentiates():
    """The public op off the chip (the dense path) with the operands, as
    ``F.flash_attention`` and under ``jax.grad``."""
    q_mask, kv_mask = _block_diffusion_operands(32, 4)
    q = _rand((1, 4, 64, 16), 0)
    k, v = _rand((1, 2, 64, 16), 1), _rand((1, 2, 64, 16), 2)
    want, vjp = jax.vjp(
        lambda q, k, v: _masked_dense(q, k, v, q_mask, kv_mask), q, k, v)
    got = mx.nd.flash_attention(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v),
        q_mask=mx.nd.array(q_mask, dtype="int32"),
        kv_mask=mx.nd.array(kv_mask, dtype="int32")).asnumpy()
    assert onp.abs(got - onp.asarray(want)).max() < 2e-5
    g = _rand(want.shape, 3)
    grads = jax.grad(lambda q, k, v: jnp.sum(P.flash_attention(
        q, k, v, False, None, None, None, None, q_mask, kv_mask) * g),
        argnums=(0, 1, 2))(q, k, v)
    for got, exp in zip(grads, vjp(g)):
        assert float(jnp.abs(got - exp).max()) < 5e-5


def test_dispatch_plans_the_mask():
    """A streamed K axis under a mask takes 1024 x 1024 blocks, the unit
    the mask skips by; the plan counts the forward's tiles; an unmasked
    plan is what it was."""
    plain = P.attention_dispatch(8192, 8192, 128, on_tpu=True, census=False)
    assert plain == {"kernel": "streaming", "block_q": 512, "block_k": 2048,
                     "layout": "bhsd", "heads_per_block": 1}
    plan = P.attention_dispatch(8192, 8192, 128, on_tpu=True, census=False,
                                masked=True, tiles_visited=80)
    assert (plan["block_q"], plan["block_k"]) == (1024, 1024)
    assert (plan["masked"], plan["tiles"]) == (True, 64)
    short = P.attention_dispatch(512, 512, 64, on_tpu=True, census=False,
                                 masked=True)
    assert (short["kernel"], short["block_k"], short["tiles"]) \
        == ("short_seq", 512, 1)
    from mxnet_tpu import telemetry
    before = telemetry.snapshot()["counters"].get(
        "attention.kernel.masked", 0)
    P.attention_dispatch(8192, 8192, 128, on_tpu=True, masked=True,
                         tiles_visited=80)
    assert telemetry.snapshot()["counters"]["attention.kernel.masked"] \
        == before + 1
    event = [e for e in telemetry.snapshot()["events"]
             if e.get("kind") == "attention_dispatch"][-1]
    assert (event["masked"], event["tiles"], event["tiles_visited"]) \
        == (True, 64, 80)


# ---------------------------------------------------------------------------
# a third integer a query: the floor under the ranks (a window's lower bound)
# ---------------------------------------------------------------------------

def _band_dense(q, k, v, window):
    """Plain float32 attention under the dense band ``i - window < j <=
    i``, GQA by repetition."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    i = jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[2])[None, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where((j <= i) & (j > i - window), s, -1e30), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# (block_q, block_k) as the two-integer test's; 6 and 9 query heads a
# key-value head (two key-value heads: rows of the merged layouts share
# theirs through the index maps)
@pytest.mark.parametrize("group", [6, 9])
@pytest.mark.parametrize("blocks", [(64, 64), (64, 128), (128, 64),
                                    (64, 512), (512, 512)])
def test_window_kernels_match_dense_band_fwd_and_three_gradients(blocks,
                                                                 group):
    """``floor <= rank <= reach`` in every BHSD kernel: a window of 70 keys
    — no multiple of any block — on a ragged row of 300; at 64 x 64 the
    tiles under the band are dead as those over the diagonal are."""
    s, window = 300, 70
    q_mask, kv_mask = (jnp.asarray(m)
                       for m in P.window_mask(1, s, s, window))
    q, g = _rand((1, 2 * group, s, 64), 0), _rand((1, 2 * group, s, 64), 3)
    k, v = _rand((1, 2, s, 64), 1), _rand((1, 2, s, 64), 2)
    bq, bk = blocks
    out, lse = P.pallas_flash_attention(
        q, k, v, block_q=bq, block_k=bk, interpret=True, return_lse=True,
        q_mask=q_mask, kv_mask=kv_mask)
    want, vjp = jax.vjp(lambda q, k, v: _band_dense(q, k, v, window),
                        q, k, v)
    assert float(jnp.abs(out - want).max()) < 2e-5
    ref = P._reference_attention(q, k, v, False, None, q_mask=q_mask,
                                 kv_mask=kv_mask)
    assert float(jnp.abs(ref - want).max()) < 2e-5
    grads = P.pallas_flash_attention_bwd(
        q, k, v, out, lse, g, block_q=bq, block_k=bk, interpret=True,
        q_mask=q_mask, kv_mask=kv_mask)
    for got, exp, name in zip(grads, vjp(g), "qkv"):
        assert got.shape == exp.shape
        assert float(jnp.abs(got - exp).max()) < 5e-5, name


def test_tile_summary_marks_exactly_the_tiles_off_the_band_as_dead():
    """``_tile_states`` against the pairs themselves, a ragged row padded
    to whole blocks: 0 exactly where a tile holds no live pair — over the
    diagonal AND under the band — 2 exactly where all are live."""
    s, padded, window = 300, 320, 70
    q_mask, kv_mask = (jnp.asarray(m)
                       for m in P.window_mask(1, s, s, window))
    qm, km = P._mask_operands(q_mask, kv_mask, padded, padded)
    assert qm.shape == (1, padded, 3) and km.shape == (1, padded, 2)
    i, j = onp.arange(padded)[:, None], onp.arange(padded)[None, :]
    seen = (j <= i) & (j > i - window) & (i < s) & (j < s)
    for bq, bk in ((64, 64), (32, 64), (64, 32), (160, 80), (16, 16)):
        states = onp.asarray(P._tile_states(qm, km, bq, bk))[0]
        tiles = seen.reshape(padded // bq, bq, padded // bk, bk)
        onp.testing.assert_array_equal(states > 0, tiles.any((1, 3)))
        onp.testing.assert_array_equal(states == 2, tiles.all((1, 3)))
    # 16 x 16: a band of 70 keys leaves whole tiles inside it, dead ones
    # under it, dead ones over the diagonal
    states = onp.asarray(P._tile_states(qm, km, 16, 16))[0]
    assert (states == 2).any() and (onp.tril(states == 0, -1)).any() \
        and (onp.triu(states == 0, 1)).any()


def test_two_integers_a_query_mean_and_cost_what_they_did():
    """A mask of two integers a query stays two in the kernels' operands
    (the block-diffusion cell's code is what it was), and is bit for bit
    the three-integer mask whose floor is under every rank."""
    q_mask, kv_mask = _block_diffusion_operands(96, 4, drop=3)
    s = q_mask.shape[1]
    qm, km = P._mask_operands(q_mask, kv_mask, 256, 256)
    assert qm.shape == (1, 256, 2) and km.shape == (1, 256, 2)
    floored = jnp.concatenate(
        [q_mask, jnp.full(q_mask.shape[:2] + (1,), -2 ** 31, jnp.int32)], -1)
    onp.testing.assert_array_equal(
        onp.asarray(P._tile_states(qm, km, 64, 64)),
        onp.asarray(P._tile_states(
            *P._mask_operands(floored, kv_mask, 256, 256), 64, 64)))
    q, g = _rand((1, 4, s, 64), 0), _rand((1, 4, s, 64), 3)
    k, v = _rand((1, 1, s, 64), 1), _rand((1, 1, s, 64), 2)
    results = []
    for mask in (q_mask, floored):
        out, lse = P.pallas_flash_attention(
            q, k, v, block_q=64, block_k=64, interpret=True,
            return_lse=True, q_mask=mask, kv_mask=kv_mask)
        results.append((out, lse) + tuple(P.pallas_flash_attention_bwd(
            q, k, v, out, lse, g, block_q=64, block_k=64, interpret=True,
            q_mask=mask, kv_mask=kv_mask)))
    for two, three in zip(*results):
        onp.testing.assert_array_equal(onp.asarray(two), onp.asarray(three))


def test_window_that_covers_the_row_is_causal_attention():
    """``window >= S``: the integers give the causal kernels' result, and
    the public op does not build them at all."""
    s = 256
    q, k, v = (_rand((1, 2, s, 64), 5 + i) for i in range(3))
    causal = P.pallas_flash_attention(q, k, v, causal=True, block_q=64,
                                      block_k=64, interpret=True)
    for window in (s, s + 100):
        q_mask, kv_mask = (jnp.asarray(m)
                           for m in P.window_mask(1, s, s, window))
        got = P.pallas_flash_attention(
            q, k, v, block_q=64, block_k=64, interpret=True, q_mask=q_mask,
            kv_mask=kv_mask)
        assert float(jnp.abs(got - causal).max()) < 2e-6
        onp.testing.assert_array_equal(
            onp.asarray(P.flash_attention(q, k, v, True, None, None, None,
                                          None, None, None, window)),
            onp.asarray(P.flash_attention(q, k, v, True)))
    with pytest.raises(ValueError, match="window=0 goes with"):
        P.flash_attention(q, k, v, True, None, None, None, None, None, None,
                          0)


def test_flash_attention_op_takes_a_window_and_differentiates():
    """The public op off the chip (the dense path) under ``window``, as
    ``F.flash_attention`` and under ``jax.grad`` inside ``jit``."""
    window = 9
    q = _rand((1, 6, 48, 16), 0)
    k, v = _rand((1, 1, 48, 16), 1), _rand((1, 1, 48, 16), 2)
    want, vjp = jax.vjp(lambda q, k, v: _band_dense(q, k, v, window),
                        q, k, v)
    got = mx.nd.flash_attention(mx.nd.array(q), mx.nd.array(k),
                                mx.nd.array(v), causal=True,
                                window=window).asnumpy()
    assert onp.abs(got - onp.asarray(want)).max() < 2e-5
    g = _rand(want.shape, 3)
    grads = jax.jit(jax.grad(lambda q, k, v: jnp.sum(P.flash_attention(
        q, k, v, True, None, None, None, None, None, None, window) * g),
        argnums=(0, 1, 2)))(q, k, v)
    for got, exp in zip(grads, vjp(g)):
        assert float(jnp.abs(got - exp).max()) < 5e-5


def test_dispatch_counts_a_window():
    from mxnet_tpu import telemetry
    before = telemetry.snapshot()["counters"].get(
        "attention.kernel.window", 0)
    plan = P.attention_dispatch(4096, 4096, 128, on_tpu=True, masked=True,
                                window=512, tiles_visited=7)
    assert plan["masked"] and plan["kernel"] == "streaming"
    # blocks no wider than the band, 512 at least, 1024 at most
    assert (plan["block_q"], plan["block_k"], plan["tiles"]) == (512, 512, 64)
    for window, side in ((100, 512), (513, 1024), (3000, 1024)):
        wide = P.attention_dispatch(4096, 4096, 128, on_tpu=True,
                                    census=False, masked=True, window=window)
        assert (wide["block_q"], wide["block_k"]) == (side, side), window
    assert telemetry.snapshot()["counters"]["attention.kernel.window"] \
        == before + 1
    event = [e for e in telemetry.snapshot()["events"]
             if e.get("kind") == "attention_dispatch"][-1]
    assert (event["window"], event["tiles_visited"]) == (512, 7)
    # a masked call that is no window says so
    P.attention_dispatch(8192, 8192, 128, on_tpu=True, masked=True)
    event = [e for e in telemetry.snapshot()["events"]
             if e.get("kind") == "attention_dispatch"][-1]
    assert event["window"] is None and event["masked"] is True
