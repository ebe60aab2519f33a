"""Expert parallelism: a top-k Mixture-of-Experts FFN sharded over an
``ep`` mesh axis with real ``lax.all_to_all`` token exchange, DROPLESS.

Reference capability: absent upstream as a named subsystem (MXNet-era
MoE lived in user code); TPU-natively this is the canonical ``ep`` axis
of the dp/tp/pp/sp/ep sharding family.  The routing and the experts'
products are the core in ``ops/moe.py`` that the one-chip block
``gluon.contrib.nn.SparseExperts`` runs too; this module adds the
exchange round it:

* tokens are sharded over ``ep`` (each device owns S = N/ndev tokens), the
  experts' weights over ``ep`` too (E/ndev a device), the router is
  replicated and picks ``k`` experts a token (one by default): k routes;
* each device groups its S k routes by DESTINATION device and sends every
  device a slot of S k rows — its routes for that device packed at the
  front, the rest padding marked "no expert" — so shapes are static and
  no route is ever dropped, however uneven the routing (a slot can hold
  all the routes of its sender);
* ``lax.all_to_all`` crosses the mesh; each device runs the core on the
  ndev*S*k rows it received (grouped by its local experts, one grouped
  product a weight, padding rows skipped), a second all_to_all returns the
  results to the token owners, which put them back in route order, apply
  the router's gates and sum a token's k;
* everything differentiates: all_to_all and the gathers are linear, the
  gate carries the softmax weight.

``moe_ffn_ref`` is the single-device oracle: a plain loop over the
experts.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import moe as _core

__all__ = ["moe_ffn_init", "moe_ffn_apply", "moe_ffn_ref"]


def moe_ffn_init(rng, hidden, ffn, n_experts, dtype=jnp.float32):
    """Parameter pytree: router (H, E), w1 (E, H, F), w2 (E, F, H)."""
    import numpy as onp
    rs = onp.random.RandomState(rng)
    s1 = 1.0 / math.sqrt(hidden)
    s2 = 1.0 / math.sqrt(ffn)
    return {
        "router": jnp.asarray(rs.randn(hidden, n_experts) * s1, dtype),
        "w1": jnp.asarray(rs.randn(n_experts, hidden, ffn) * s1, dtype),
        "w2": jnp.asarray(rs.randn(n_experts, ffn, hidden) * s2, dtype),
    }


def _route(x, router_w, k):
    """Replicated router: (expert (S, k), gate (S, k)), the k largest of
    the softmax (ties to the lower index) and their probabilities."""
    probs = jax.nn.softmax(
        x.astype(jnp.float32) @ router_w.astype(jnp.float32), axis=-1)
    return _core.topk_route(probs, k)


def moe_ffn_apply(params, x, mesh: Mesh, axis: str = "ep", k: int = 1):
    """MoE FFN over token-sharded input x (N, H) → (N, H), ``k`` experts a
    token.

    ``params['w1']/['w2']`` leading (expert) dim shards over ``axis``;
    the router is replicated.  N must divide by the axis size.
    """
    ndev = mesh.shape[axis]
    E = params["w1"].shape[0]
    if E % ndev:
        raise ValueError("n_experts %d must divide over %r size %d"
                         % (E, axis, ndev))
    N, H = x.shape
    if N % ndev:
        raise ValueError("token count %d must shard over %r size %d"
                         % (N, axis, ndev))
    S = N // ndev
    R = S * k                                       # routes a device
    E_loc = E // ndev

    def per_shard(params, xl):
        xl32 = xl.astype(jnp.float32)               # (S, H) local tokens
        expert, gate = _route(xl, params["router"], k)
        routes = expert.T.reshape(-1)               # route r: token r % S
        # my routes grouped by destination device, one R-row slot each
        order, place, to_dev = _core.group_by_expert(routes // E_loc, 0,
                                                     ndev)
        start = jnp.cumsum(to_dev) - to_dev                     # (ndev,)
        slot_row = jnp.arange(R)[None, :]                       # (1, R)
        src = jnp.minimum(start[:, None] + slot_row, R - 1)     # (ndev, R)
        filled = slot_row < to_dev[:, None]
        sorted_x = _core.spread_rows(xl32, order, place)
        sorted_e = jnp.take(routes % E_loc, order)
        send_x = jnp.where(filled[..., None], sorted_x[src], 0.0)
        send_e = jnp.where(filled, sorted_e[src], E_loc)   # E_loc: padding
        recv_x = lax.all_to_all(send_x, axis, 0, 0)         # (ndev, R, H)
        recv_e = lax.all_to_all(send_e, axis, 0, 0)         # (ndev, R)
        # my experts on everything I received; params["w1"]/["w2"] arrive
        # as the LOCAL (E_loc, ...) expert slice (in_specs P(axis))
        done, _ = _core.sparse_ffn(
            recv_x.reshape(ndev * R, H), recv_e.reshape(ndev * R),
            jnp.ones((ndev * R,), jnp.float32),
            _core.mlp_experts(params["w1"].astype(jnp.float32),
                              params["w2"].astype(jnp.float32),
                              jax.nn.gelu), 0, E_loc)
        back = lax.all_to_all(done.reshape(ndev, R, H), axis, 0, 0)
        # slot (d, j) holds the result of my sorted route start[d] + j
        dev_sorted = jnp.take(routes // E_loc, order)
        sorted_out = back[dev_sorted,
                          jnp.arange(R) - jnp.take(start, dev_sorted)]
        out = jnp.take(sorted_out, place, axis=0) \
            * gate.T.reshape(-1)[:, None]
        return out.reshape(k, S, H).sum(axis=0).astype(x.dtype)

    in_specs = ({"router": P(), "w1": P(axis), "w2": P(axis)}, P(axis))
    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                       out_specs=P(axis), check_vma=False)
    return fn(params, x)


def moe_ffn_ref(params, x, k: int = 1):
    """Single-device oracle: every expert on every token in a plain loop,
    each token keeping its own experts' rows times the router's gates."""
    expert, gate = _route(x, params["router"], k)
    x32 = x.astype(jnp.float32)
    out = jnp.zeros_like(x32)
    for e in range(params["w1"].shape[0]):
        y = jax.nn.gelu(x32 @ params["w1"][e].astype(jnp.float32)) \
            @ params["w2"][e].astype(jnp.float32)
        out = out + jnp.where(expert == e, gate, 0.0).sum(
            -1, keepdims=True) * y
    return out.astype(x.dtype)
