"""Fused LayerNorm Pallas kernels (CPU: interpret mode; the same kernels
run compiled on the real chip inside every transformer LN site).

Reference role: ``src/operator/nn/layer_norm.cc`` — the reference ships
a hand-fused LayerNorm for the same reason."""
import numpy as onp
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops import pallas_layernorm as pln


def _mk(n, c, dtype, seed=0):
    rs = onp.random.RandomState(seed)
    x = jnp.asarray(rs.randn(n, c).astype("float32"), dtype)
    g = jnp.asarray((rs.rand(c) + 0.5).astype("float32"), dtype)
    b = jnp.asarray((rs.randn(c) * 0.1).astype("float32"), dtype)
    return x, g, b


def _f32_oracle(x, g, b, eps=1e-5):
    d = x.astype(jnp.float32)
    mu = d.mean(-1, keepdims=True)
    xc = d - mu
    var = (xc * xc).mean(-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 0.05)])
@pytest.mark.parametrize("n", [64, 100])  # 100: padded final block
def test_fwd_kernel_matches_oracle(dtype, tol, n):
    x, g, b = _mk(n, 256, dtype)
    y, mu, rstd = pln.pallas_layer_norm_fwd(x, g, b, 1e-5, block_rows=32,
                                            interpret=True)
    ref = _f32_oracle(x, g, b)
    assert float(jnp.abs(y.astype(jnp.float32) - ref).max()) < tol
    assert mu.shape == (n, 1) and rstd.shape == (n, 1)


def test_bwd_kernel_matches_f32_vjp():
    """dx/dgamma/dbeta against an fp32 autodiff oracle on the SAME
    quantized inputs; dg/db accumulate in fp32 scratch so they match at
    fp32 precision even for bf16 operands."""
    x, g, b = _mk(100, 256, jnp.bfloat16, seed=1)
    ct = jnp.asarray(onp.random.RandomState(2).randn(100, 256)
                     .astype("float32"), jnp.bfloat16)
    xq, gq, bq, cq = (a.astype(jnp.float32) for a in (x, g, b, ct))
    _, vjp = jax.vjp(lambda d, gg, bb: _f32_oracle(d, gg, bb), xq, gq, bq)
    rdx, rdg, rdb = vjp(cq)

    y, mu, rstd = pln.pallas_layer_norm_fwd(x, g, b, 1e-5, block_rows=32,
                                            interpret=True)
    dx, dg, db = pln.pallas_layer_norm_bwd(x, g, mu, rstd, ct,
                                           block_rows=32, interpret=True)
    assert float(jnp.abs(dg - rdg).max()) / float(jnp.abs(rdg).max()) < 1e-5
    assert float(jnp.abs(db - rdb).max()) / float(jnp.abs(rdb).max()) < 1e-5
    assert float(jnp.abs(dx.astype(jnp.float32) - rdx).max()) < 0.05


def test_fused_layer_norm_grads_match_jnp_fallback():
    """The public custom-vjp op (jnp fallback off-TPU) differentiates
    like the plain composition."""
    x, g, b = _mk(24, 128, jnp.float32, seed=3)

    def fused(a, gg, bb):
        return jnp.sum(pln.fused_layer_norm(a, gg, bb, 1e-5) ** 2)

    def plain(a, gg, bb):
        return jnp.sum(pln._jnp_ln(a, gg, bb, 1e-5) ** 2)

    g1 = jax.grad(fused, argnums=(0, 1, 2))(x, g, b)
    g2 = jax.grad(plain, argnums=(0, 1, 2))(x, g, b)
    for a, bb in zip(g1, g2):
        assert float(jnp.abs(a - bb).max()) < 1e-4


def test_layer_norm_op_routes_axis_and_mean_var():
    """The registry op keeps the generic path for non-last axes."""
    from mxnet_tpu.ops.nn import layer_norm
    rs = onp.random.RandomState(5)
    x = jnp.asarray(rs.randn(4, 6, 8).astype("float32"))
    g = jnp.asarray(rs.rand(6).astype("float32") + 0.5)
    b = jnp.asarray(rs.randn(6).astype("float32"))
    out = layer_norm(x, g, b, axis=1)
    ref = _f32_oracle(jnp.swapaxes(x, 1, 2), g, b)
    assert float(jnp.abs(jnp.swapaxes(out, 1, 2) - ref).max()) < 1e-5


def test_huge_channel_falls_back_to_generic_path():
    """C too large for the VMEM budget routes to the jnp path instead of
    a Mosaic compile failure (block picker returns None)."""
    assert pln._pick_block_rows(768) is not None
    assert pln._pick_block_rows(10 ** 6) is None
    x = jnp.asarray(onp.random.RandomState(0).randn(4, 8).astype("f"))
    g = jnp.ones(8); b = jnp.zeros(8)
    out = pln.fused_layer_norm(x, g, b, 1e-5)  # CPU: fallback either way
    ref = pln._jnp_ln(x, g, b, 1e-5)
    assert float(jnp.abs(out - ref).max()) < 1e-6


def test_default_layer_norm_supports_forward_mode():
    """The default LayerNorm path must stay jvp-differentiable (the
    fused custom_vjp kernels are opt-in via MXNET_FUSED_LAYERNORM=1
    precisely because custom_vjp breaks forward mode)."""
    from mxnet_tpu.ops.nn import layer_norm
    x = jnp.asarray(onp.random.RandomState(0).randn(4, 16).astype("f"))
    g = jnp.ones(16)
    b = jnp.zeros(16)
    out, tangent = jax.jvp(lambda a: layer_norm(a, g, b), (x,),
                           (jnp.ones_like(x),))
    assert out.shape == tangent.shape == x.shape


def test_fused_kernels_mixed_dtype_promotes_like_composition():
    """bf16 data with fp32 affine params: the kernel's output dtype and
    values match the composed jnp expression (partial-AMP models)."""
    rs = onp.random.RandomState(4)
    x = jnp.asarray(rs.randn(16, 128).astype("float32"), jnp.bfloat16)
    g = jnp.asarray((rs.rand(128) + 0.5).astype("float32"))
    b = jnp.asarray(rs.randn(128).astype("float32"))
    y, _, _ = pln.pallas_layer_norm_fwd(x, g, b, 1e-5, block_rows=8,
                                        interpret=True)
    ref = pln._jnp_ln(x, g, b, 1e-5)
    assert y.dtype == ref.dtype == jnp.float32
    assert float(jnp.abs(y - ref).max()) < 0.02


def test_fused_env_knob_routes_to_kernels(monkeypatch):
    """MXNET_FUSED_LAYERNORM=1 flips the op onto the fused path (jnp
    fallback on CPU, same values)."""
    from mxnet_tpu.ops.nn import layer_norm
    monkeypatch.setenv("MXNET_FUSED_LAYERNORM", "1")
    rs = onp.random.RandomState(6)
    x = jnp.asarray(rs.randn(4, 32).astype("f"))
    g = jnp.asarray((rs.rand(32) + 0.5).astype("f"))
    b = jnp.asarray(rs.randn(32).astype("f"))
    out = layer_norm(x, g, b)
    ref = pln._jnp_ln(x, g, b, 1e-5)
    assert float(jnp.abs(out - ref).max()) < 1e-5
