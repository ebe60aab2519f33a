"""Expert parallelism: dropless top-k MoE FFN over the ``ep`` mesh axis
(all_to_all token exchange round the core of ``ops/moe.py``) vs the
single-device oracle, a plain loop over the experts, on the virtual
8-device CPU mesh."""
import numpy as onp
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from mxnet_tpu import parallel

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >=4 devices (virtual CPU mesh)")


def _setup(ndev, E, N, H, F, seed=1):
    mesh = Mesh(onp.array(jax.devices()[:ndev]), ("ep",))
    params = parallel.moe_ffn_init(0, hidden=H, ffn=F, n_experts=E)
    x = jnp.asarray(onp.random.RandomState(seed).randn(N, H)
                    .astype("float32"))
    return mesh, params, x


@pytest.mark.parametrize("ndev,E,N,H,F", [
    (4, 8, 48, 8, 16),        # 2 experts per device
    (8, 8, 64, 16, 32),       # 1 expert per device
    (8, 16, 128, 32, 64),     # 2 experts per device, bigger
])
def test_moe_matches_oracle(ndev, E, N, H, F):
    if len(jax.devices()) < ndev:
        pytest.skip("not enough devices")
    mesh, params, x = _setup(ndev, E, N, H, F)
    got = parallel.moe_ffn_apply(params, x, mesh)
    want = parallel.moe_ffn_ref(params, x)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-6)


def test_moe_two_experts_a_token_matches_oracle():
    """k = 2 through the same core: 2 S routes a device go through one
    grouping, a token's two results come back and are summed with their
    gates; value and gradients against the plain loop."""
    mesh, params, x = _setup(4, 8, 48, 8, 16)
    got = parallel.moe_ffn_apply(params, x, mesh, k=2)
    want = parallel.moe_ffn_ref(params, x, k=2)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-6)
    assert onp.abs(onp.asarray(want - parallel.moe_ffn_ref(params, x))
                   ).max() > 1e-3          # the second expert adds something
    g1 = jax.grad(lambda p: jnp.sum(
        parallel.moe_ffn_apply(p, x, mesh, k=2) ** 2))(params)
    g2 = jax.grad(lambda p: jnp.sum(
        parallel.moe_ffn_ref(p, x, k=2) ** 2))(params)
    for name in g1:
        onp.testing.assert_allclose(onp.asarray(g1[name]),
                                    onp.asarray(g2[name]),
                                    rtol=1e-4, atol=2e-4, err_msg=name)


def test_moe_grads_match_oracle():
    ndev = min(8, len(jax.devices()))
    mesh, params, x = _setup(ndev, 8, 8 * ndev, 16, 32)

    g1 = jax.grad(lambda p: jnp.sum(
        parallel.moe_ffn_apply(p, x, mesh) ** 2))(params)
    g2 = jax.grad(lambda p: jnp.sum(
        parallel.moe_ffn_ref(p, x) ** 2))(params)
    for k in g1:
        onp.testing.assert_allclose(onp.asarray(g1[k]),
                                    onp.asarray(g2[k]),
                                    rtol=1e-4, atol=2e-4, err_msg=k)


def test_moe_overflow_drops_no_token():
    """Routing so uneven that one expert gets most of every shard's tokens
    — the case a per-expert capacity used to drop — computes every token:
    the result is the oracle's and no row rides the residual as zeros."""
    ndev = 4
    if len(jax.devices()) < ndev:
        pytest.skip("not enough devices")
    mesh, params, x = _setup(ndev, 4, 32, 8, 16, seed=3)
    # a router that sends nearly every token to expert 2 (on device 2)
    params = {**params, "router": params["router"].at[:, 2].add(
        10.0 * jnp.sign(params["router"][:, 2]))}
    x = jnp.abs(x) * jnp.sign(params["router"][:, 2])[None, :]
    got = parallel.moe_ffn_apply(params, x, mesh)
    want = parallel.moe_ffn_ref(params, x)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-6)
    logits = onp.asarray(x @ params["router"])
    assert (logits.argmax(-1) == 2).mean() > 0.8
    # no token row is zero (dropped)
    assert not (onp.abs(onp.asarray(got)).sum(axis=1) == 0).any()


def test_moe_validation_errors():
    mesh, params, x = _setup(4, 8, 48, 8, 16)
    with pytest.raises(ValueError):
        parallel.moe_ffn_apply({**params,
                                "w1": params["w1"][:6],
                                "w2": params["w2"][:6]}, x, mesh)
    with pytest.raises(ValueError):
        parallel.moe_ffn_apply(params, x[:30], mesh)
